"""Unified ``Retriever`` facade: one planned pipeline for local, batched,
and document-sharded WARP search.

WARP's contribution is an *engine* — WARP_SELECT, implicit decompression,
and the two-stage reduction composed into one optimized pipeline — and this
module is the single front door to it. The API has an explicit plan/execute
split:

  build / from_index   construct (or adopt) a single-device ``WarpIndex``,
                       a ``ShardedWarpIndex`` + mesh, or a
                       ``SegmentedWarpIndex`` (base + delta segments).
  from_store           adopt a saved index directory (``repro.store``) as
                       zero-copy mmap views — single, sharded, or
                       base-plus-deltas.
  plan(config)         validate the search config against index geometry
                       and backend capabilities, materialize every
                       data-dependent default (t', k_impute, executor), and
                       compile the jit'd callables once -> ``SearchPlan``.
  retrieve(...)        dispatch a single query through a plan.
  retrieve_batch(...)  dispatch a [B, Q, D] query batch through a plan.

Every execution surface — ``engine.search``, ``engine.search_batch``,
``distributed.sharded_search``, the serving batcher, benchmarks — runs the
same three exported stages (``warp_select`` -> ``score_probed_clusters`` ->
``two_stage_reduce``); the plan only decides *how* they run:

  gather   = "materialize" | "fused"       candidate-code movement
  executor = "auto" | "kernel" | "reference"  Pallas vs jnp (auto = backend)
  memory   = "full" | "scan_qtokens"       peak working-set bounding
  layout   = "dense" | "ragged" | "auto"   candidate shape: padded
             [Q, nprobe, cap] grid vs flat tile worklist sized by the real
             candidates (auto = by measured padding waste at plan time)

Ragged plans are **query-adaptive**: resolution records a bucket ladder
(``core.worklist.bucket_ladder`` — ascending power-of-two worklist tile
bounds topped by the static worst case) and every retrieve dispatches to
the pipeline compiled for the smallest bucket that fits the query's actual
probe set, so compute and the reduction's sort-N track the real candidate
demand with no per-query recompilation. Bucket selection is a tiny
host-side reduction over the WARP_SELECT probe sizes; on sharded indexes
it resolves as the max over shards (the ``shard_map`` body stays one
unbranched program), on segmented indexes over combined per-segment tile
counts. Any fitting bucket yields bit-identical top-k doc ids (smaller
buckets only trim all-padding tiles).

Plans are cached per config, so repeated ``retrieve`` calls with the same
config reuse the compiled pipeline (per-bucket compilation is lazy and
cached inside the plan).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import distributed as dist
from repro.core import docfilter as df
from repro.core import engine
from repro.core import worklist as wl
from repro.core.index import build_index
from repro.core.reduction import TopKResult
from repro.core.types import IndexBuildConfig, WarpIndex, WarpSearchConfig
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.obs import STATE as _OBS

__all__ = ["Retriever", "SearchPlan", "K_LADDER", "ladder_rung", "laddered_config"]


# ---------------------------------------------------------------------------
# k-laddered config resolution
# ---------------------------------------------------------------------------

# Per-k retrieval hyperparameter ladder, mirroring the reference searcher's
# k-laddered defaults: small k needs few probes; deep result lists need a
# wider probe set, a deeper imputation scan, and a larger t' so the missing
# similarity estimate stays calibrated over more candidates. Each rung is
# (k upper bound inclusive — None = unbounded, rung name, overrides).
K_LADDER = (
    (10, "small", dict(nprobe=16, k_impute=32, t_prime_scale=0.5)),
    (100, "medium", dict(nprobe=32, k_impute=64, t_prime_scale=1.0)),
    (None, "large", dict(nprobe=64, k_impute=128, t_prime_scale=2.0)),
)


def ladder_rung(k: int) -> tuple[str, dict]:
    """(rung name, parameter overrides) for a requested result depth."""
    for bound, name, params in K_LADDER:
        if bound is None or k <= bound:
            return name, params
    raise AssertionError("unreachable: ladder has an unbounded rung")


def laddered_config(
    k: int,
    config: WarpSearchConfig | None = None,
    *,
    n_tokens: int | None = None,
    n_centroids: int | None = None,
) -> WarpSearchConfig:
    """Resolve per-request retrieval hyperparameters from the requested
    ``k`` (``K_LADDER``), with explicit settings taking precedence.

    A field of ``config`` that differs from the ``WarpSearchConfig``
    dataclass default is treated as pinned by the caller and never
    overridden; fields left at their defaults take the ladder value for
    ``k``'s rung. With index geometry (``n_tokens`` / ``n_centroids``) the
    ladder also concretizes ``t_prime`` (``t_prime_scale * sqrt(n_tokens)``,
    clamped) and clamps ``nprobe`` to the centroid count — without it those
    stay data-dependent and resolve at plan time as before.
    """
    base = config if config is not None else WarpSearchConfig()
    default = WarpSearchConfig()
    _, params = ladder_rung(int(k))
    kw: dict = {"k": int(k)}
    if base.nprobe == default.nprobe:
        nprobe = int(params["nprobe"])
        if n_centroids is not None:
            nprobe = max(1, min(nprobe, int(n_centroids)))
        kw["nprobe"] = nprobe
    if base.k_impute == default.k_impute:
        kw["k_impute"] = int(params["k_impute"])
    if base.t_prime is None and n_tokens:
        tp = int(params["t_prime_scale"] * (int(n_tokens) ** 0.5))
        kw["t_prime"] = max(1, min(tp, base.t_prime_max, int(n_tokens)))
    return dataclasses.replace(base, **kw)


@dataclasses.dataclass(frozen=True, eq=False)
class SearchPlan:
    """A validated, compiled search pipeline bound to one index + config.

    ``config`` is fully resolved: ``t_prime`` / ``k_impute`` are concrete
    ints, ``executor`` is "kernel" or "reference" (never "auto"). The jit'd
    callables are built once at plan time; ``retrieve``/``retrieve_batch``
    only convert inputs and dispatch.

    ``eq=False``: plans hash/compare by identity — they close over compiled
    callables and device arrays, which have no useful value equality.
    """

    config: WarpSearchConfig
    n_shards: int
    backend: str
    index_geometry: dict
    _single: Callable[..., TopKResult] = dataclasses.field(repr=False)
    _batch: Callable[..., TopKResult] = dataclasses.field(repr=False)
    _index: Any = dataclasses.field(repr=False)
    # Host-side bucket probe of the adaptive ragged dispatcher (None on
    # dense / single-rung plans): (q, qmask) -> chosen worklist bucket.
    _bucket_for: Any = dataclasses.field(repr=False, default=None)
    # Forced-rung batch dispatch (None on non-adaptive plans):
    # bucket -> compiled (index, q, qmask) -> TopKResult at that rung.
    _batch_at: Any = dataclasses.field(repr=False, default=None)
    # Executor fallback (kernel plans only): a zero-arg factory compiling
    # the same pipeline with executor="reference" (bit-identical results),
    # invoked when the kernel path fails at warmup or dispatch.
    _fallback_factory: Any = dataclasses.field(repr=False, default=None)
    # Mutable fallback state (the dataclass is frozen; the dict is not):
    # {"active", "warned", "error", "single", "batch", "batch_at"}.
    _fallback: dict = dataclasses.field(repr=False, default_factory=dict)
    # ``DocFilter.describe()`` of a filtered plan (None unfiltered) — part
    # of the describe()/fingerprint() snapshot, so a filtered plan can
    # never alias an unfiltered (or differently filtered) one in caches.
    filter_info: dict | None = None

    @property
    def t_prime(self) -> int:
        return self.config.t_prime

    @property
    def k_impute(self) -> int:
        return self.config.k_impute

    def retrieve(self, q: jax.Array, qmask: jax.Array | None = None) -> TopKResult:
        """One query: q f32[Q, D] -> TopKResult (scores f32[k], doc_ids i32[k])."""
        q = jnp.asarray(q, jnp.float32)
        if qmask is None:
            qmask = jnp.ones((q.shape[0],), bool)
        return self._dispatch(q, jnp.asarray(qmask, bool), kind="single")

    def retrieve_batch(self, q: jax.Array, qmask: jax.Array | None = None) -> TopKResult:
        """Query batch: q f32[B, Q, D] -> TopKResult with leading batch dim."""
        q = jnp.asarray(q, jnp.float32)
        if qmask is None:
            qmask = jnp.ones(q.shape[:2], bool)
        return self._dispatch(q, jnp.asarray(qmask, bool), kind="batch")

    # ---- executor fallback ----
    @property
    def fallback_active(self) -> bool:
        """Whether a kernel-path failure demoted this plan to the
        reference executor (bit-identical results, no Pallas)."""
        return bool(self._fallback.get("active"))

    def warmup(self) -> bool:
        """Compile-and-run the plan once on a dummy query so kernel-path
        failures (lowering, launch) surface HERE, not on the first real
        request. On failure the plan demotes itself to the reference
        executor; returns True iff the fallback was activated. No-op on
        plans already resolved to the reference executor."""
        if self.config.executor != "kernel" or self._fallback_factory is None:
            return False
        if self._fallback.get("active"):
            return True
        geo = self.index_geometry
        q = jnp.zeros((2, geo["dim"]), jnp.float32)
        qmask = jnp.ones((2,), bool)
        try:
            jax.block_until_ready(self._single(self._index, q, qmask))
        except Exception as e:  # noqa: BLE001 — any kernel failure demotes
            self._activate_fallback(e)
            return True
        return False

    def _activate_fallback(self, exc: BaseException) -> None:
        single, batch, batch_at = self._fallback_factory()
        fb = self._fallback
        fb.update(
            single=single, batch=batch, batch_at=batch_at,
            error=repr(exc), active=True,
        )
        obs.count("warp_executor_fallbacks_total")
        if not fb.get("warned"):
            fb["warned"] = True
            warnings.warn(
                f"kernel executor failed ({exc!r}); plan demoted to the "
                "bit-identical reference executor "
                "(warp_executor_fallbacks_total)",
                stacklevel=3,
            )

    def _active_fn(self, kind: str, bucket=None):
        """The compiled callable for a dispatch kind, honoring fallback."""
        fb = self._fallback
        if fb.get("active"):
            if kind == "batch_at":
                return fb["batch_at"](bucket)
            return fb[kind]
        if kind == "single":
            return self._single
        if kind == "batch":
            return self._batch
        return self._batch_at(bucket)

    def _dispatch(self, q, qmask, *, kind: str, bucket=None) -> TopKResult:
        """Observability-aware dispatch (``repro.obs.STATE``).

        Every state runs the same compiled callable; nothing about the
        program changes with observability, so results are bit-identical.
        The call sits in one ``retrieve`` span (``obs.span``: a tracer
        span when a tracer is installed, a profiler annotation while a
        profiler session records; nothing otherwise), without a fence:
        a retrieve only enqueues, and the stage times come from the
        device trace, where every op carries its stage's ``warp.*``
        scope (``engine.STAGE_SCOPES``). Metrics-only adds the
        ``warp_retrieve_seconds`` histogram, which blocks once on the
        result (a latency over async dispatch would time the enqueue).

        Kernel plans get one safety net on top: a failure escaping the
        compiled callable demotes the plan to the reference executor
        (``_activate_fallback``) and the dispatch reruns there — the
        lazy counterpart to ``warmup()`` for failures that only strike a
        specific shape/bucket.
        """
        try:
            return self._dispatch_modes(q, qmask, kind=kind, bucket=bucket)
        except Exception as e:  # noqa: BLE001
            if (
                self.config.executor != "kernel"
                or self._fallback_factory is None
                or self._fallback.get("active")
            ):
                raise
            self._activate_fallback(e)
            return self._dispatch_modes(q, qmask, kind=kind, bucket=bucket)

    def _dispatch_modes(self, q, qmask, *, kind: str, bucket=None) -> TopKResult:
        fn = self._active_fn(kind, bucket)
        reg = _OBS.metrics
        with obs.span(
            "retrieve", kind=kind, layout=self.config.layout,
            n_shards=self.n_shards,
        ):
            if reg is None:
                return fn(self._index, q, qmask)
            t0 = time.perf_counter()
            out = fn(self._index, q, qmask)
            jax.block_until_ready(out)
            self._obs_retrieve(reg, kind, time.perf_counter() - t0)
            return out

    @staticmethod
    def _obs_retrieve(reg, kind: str, dt: float) -> None:
        reg.counter(
            "warp_retrieves_total",
            "Retrieve dispatches through SearchPlan", kind=kind,
        ).inc()
        reg.histogram(
            "warp_retrieve_seconds",
            "End-to-end retrieve latency at the plan boundary", kind=kind,
        ).observe(dt)

    def retrieve_batch_at(
        self, q: jax.Array, qmask: jax.Array | None = None, *, bucket: int
    ) -> TopKResult:
        """Query batch at a FORCED worklist rung (adaptive plans only).

        ``bucket`` must be a ladder rung that fits every batch element's
        true tile demand — the bucket-aware scheduler guarantees this by
        grouping requests by their admission-time ``adaptive_bucket`` and
        dispatching each batch at the max rung of its members. Any
        fitting rung returns top-k doc ids bit-identical to
        ``retrieve_batch`` (worklist exactness: smaller rungs only trim
        all-padding tiles); an under-sized rung would silently truncate,
        hence the ladder-membership check.
        """
        if self._batch_at is None:
            raise ValueError(
                "retrieve_batch_at needs an adaptive ragged plan "
                "(layout='ragged' with a multi-rung bucket ladder)"
            )
        if bucket not in (self.config.worklist_buckets or ()):
            raise ValueError(
                f"bucket {bucket} is not a rung of this plan's ladder "
                f"{self.config.worklist_buckets}"
            )
        q = jnp.asarray(q, jnp.float32)
        if qmask is None:
            qmask = jnp.ones(q.shape[:2], bool)
        return self._dispatch(
            q, jnp.asarray(qmask, bool), kind="batch_at", bucket=bucket
        )

    def adaptive_bucket(self, q: jax.Array, qmask: jax.Array | None = None) -> int | None:
        """The worklist bucket the adaptive dispatcher would run this
        single query with (q f32[Q, D]) — the smallest ladder rung that
        fits the query's actual probe tile demand. ``None`` on plans with
        no adaptive dispatch (dense layout, or a single-rung ladder).
        Benchmarks snapshot this next to ``describe()`` so recorded
        numbers name the bucket that ran."""
        if self._bucket_for is None:
            return None
        q = jnp.asarray(q, jnp.float32)
        if qmask is None:
            qmask = jnp.ones(q.shape[:-1], bool)
        return self._bucket_for(q, jnp.asarray(qmask, bool))

    def describe(self) -> dict:
        """Snapshot of every resolved pipeline choice (JSON-serializable) —
        recorded by benchmarks so perf numbers name the plan that ran.

        The layout block reports *expected occupancy*: how many candidate
        slots per query token each layout pays for (``slots_per_qtoken`` —
        also the reduction's sort N per token) vs the dense
        ``nprobe * cap`` baseline, and the fraction of those slots the mean
        cluster size actually fills. A dense plan with low
        ``expected_slot_occupancy`` is the signal to migrate to
        ``layout="ragged"`` (or "auto"); see README "Performance tuning".

        The snapshot carries a ``fingerprint`` — a short stable hash of
        every other field (see ``fingerprint()``); the serving cache keys
        results on it so two plans that resolved identically share
        entries and any resolved difference (nprobe, layout, tile, k,
        geometry, ...) keeps them apart.
        """
        d = self._describe_core()
        d["fingerprint"] = self.fingerprint()
        return d

    def fingerprint(self) -> str:
        """Stable 16-hex-digit digest of the resolved plan snapshot
        (``describe()`` minus the fingerprint itself) — the plan
        component of serving cache keys."""
        blob = json.dumps(
            self._describe_core(), sort_keys=True, default=str
        ).encode()
        return hashlib.sha1(blob).hexdigest()[:16]

    def _describe_core(self) -> dict:
        cfg = self.config
        geo = self.index_geometry
        cap = geo["cap"]
        tile = ops.resolve_tile_c(cap, cfg.tile_c, layout=cfg.layout)
        dense_slots = cfg.nprobe * cap
        if cfg.layout == "ragged" and cfg.worklist_tiles is not None:
            slots = cfg.worklist_tiles * tile
        else:
            slots = dense_slots
        mean_cluster = geo["n_tokens"] / max(
            1, self.n_shards * geo["n_centroids"]
        )
        expected_real = min(dense_slots, cfg.nprobe * mean_cluster)
        return {
            "gather": cfg.gather,
            "executor": cfg.executor,
            "memory": cfg.memory,
            "layout": cfg.layout,
            "tile_c": tile,
            # Tile provenance: "config" (explicit override), "autotune"
            # (measured entry from kernels/autotune.py matched this index
            # geometry on this backend), or "heuristic" (analytic
            # fallback); the DMA schedule rides with it.
            "tile_source": cfg.tile_source or "heuristic",
            "buffering": cfg.buffering,
            "worklist_tiles": cfg.worklist_tiles,
            # The adaptive bucket ladder (None on dense plans); the top
            # rung equals worklist_tiles. The bucket actually chosen is
            # per-query — see ``adaptive_bucket``.
            "worklist_buckets": (
                list(cfg.worklist_buckets) if cfg.worklist_buckets else None
            ),
            "slots_per_qtoken": slots,
            "dense_slots_per_qtoken": dense_slots,
            "expected_slot_occupancy": round(
                expected_real / max(1, slots), 4
            ),
            "reduce_impl": cfg.reduce_impl,
            "sum_impl": cfg.sum_impl,
            "nprobe": cfg.nprobe,
            "t_prime": cfg.t_prime,
            "k": cfg.k,
            # The K_LADDER rung this plan's k falls in — the label
            # ``plan_for_k`` resolved defaults from (explicit settings
            # still override; see ``laddered_config``).
            "k_ladder": ladder_rung(cfg.k)[0],
            "k_impute": cfg.k_impute,
            "n_shards": self.n_shards,
            "backend": self.backend,
            # Filter identity (None unfiltered): kind/survivors/digest —
            # fingerprints of a filtered and an unfiltered plan (or two
            # different filters) can never collide.
            "filter": self.filter_info,
            **geo,
        }


class Retriever:
    """Facade over the WARP engine: build/adopt an index, plan, retrieve.

    >>> r = Retriever.build(emb, token_doc_ids, n_docs)
    >>> plan = r.plan(WarpSearchConfig(nprobe=16, k=10, gather="fused"))
    >>> res = plan.retrieve(q, qmask)          # or r.retrieve(q, qmask, config=...)

    A ``Retriever`` wraps a single-device ``WarpIndex``, a
    ``ShardedWarpIndex`` (+ mesh), or a ``SegmentedWarpIndex`` (a frozen
    base plus delta segments from ``repro.store``); the planned pipeline is
    identical — the sharded plan runs it per shard under ``shard_map`` with
    globally aligned imputation and an O(k · devices) merge, the segmented
    plan runs stage 1 once over combined cluster sizes and merges the
    per-segment reductions with doc-id offsets.
    """

    def __init__(
        self,
        index,
        *,
        mesh: jax.sharding.Mesh | None = None,
        shard_axes: tuple[str, ...] = ("data",),
    ):
        self.index = index
        self.shard_axes = shard_axes
        # Keyed by (config, filter digest | None): filtered plans never
        # alias unfiltered ones, and equal-survivor filters share a plan.
        self._plans: dict[tuple, SearchPlan] = {}
        if self.is_segmented and mesh is not None:
            raise ValueError("mesh= does not apply to a SegmentedWarpIndex")
        if self.is_sharded:
            if mesh is None:
                mesh = make_mesh((index.n_shards,), ("data",))
                self.shard_axes = ("data",)
            mesh_size = 1
            for ax in self.shard_axes:
                mesh_size *= mesh.shape[ax]
            if mesh_size != index.n_shards:
                raise ValueError(
                    f"mesh axes {self.shard_axes} have total size {mesh_size} "
                    f"but the index has {index.n_shards} shards"
                )
        elif mesh is not None:
            raise ValueError("mesh= only applies to a ShardedWarpIndex")
        self.mesh = mesh

    # ---- constructors ----
    @classmethod
    def build(
        cls,
        embeddings,
        token_doc_ids,
        n_docs: int,
        index_cfg: IndexBuildConfig = IndexBuildConfig(),
        *,
        n_shards: int | None = None,
        mesh: jax.sharding.Mesh | None = None,
        shard_axes: tuple[str, ...] = ("data",),
    ) -> "Retriever":
        """Index a corpus. ``n_shards``/``mesh`` select the document-sharded
        build (n_shards defaults to the mesh size when only a mesh is given)."""
        if mesh is not None and n_shards is None:
            n_shards = 1
            for ax in shard_axes:
                n_shards *= mesh.shape[ax]
        if n_shards is None:
            index = build_index(embeddings, token_doc_ids, n_docs, index_cfg)
            return cls(index)
        sidx = dist.build_sharded_index(
            embeddings, token_doc_ids, n_docs, n_shards, index_cfg,
            mesh=mesh, shard_axes=shard_axes,
        )
        return cls(sidx, mesh=mesh, shard_axes=shard_axes)

    @classmethod
    def from_index(
        cls,
        index,
        *,
        mesh: jax.sharding.Mesh | None = None,
        shard_axes: tuple[str, ...] = ("data",),
    ) -> "Retriever":
        """Adopt an existing single-device, sharded, or segmented index."""
        return cls(index, mesh=mesh, shard_axes=shard_axes)

    @classmethod
    def from_store(
        cls,
        path: str,
        *,
        mmap: bool = True,
        with_segments: bool = True,
        mesh: jax.sharding.Mesh | None = None,
        shard_axes: tuple[str, ...] = ("data",),
    ) -> "Retriever":
        """Adopt a saved index directory (``repro.store.save_index`` /
        ``launch/build_index.py``). With ``mmap`` (default) the arrays are
        zero-copy ``np.memmap`` views; delta segments are picked up
        automatically unless ``with_segments=False``."""
        from repro.store import load_index  # deferred: store depends on core

        index = load_index(path, mmap=mmap, with_segments=with_segments)
        return cls(index, mesh=mesh, shard_axes=shard_axes)

    # ---- properties ----
    @property
    def is_sharded(self) -> bool:
        return isinstance(self.index, dist.ShardedWarpIndex)

    @property
    def is_segmented(self) -> bool:
        # Deferred import keeps core importable without the store package.
        from repro.store.segments import SegmentedWarpIndex

        return isinstance(self.index, SegmentedWarpIndex)

    @property
    def n_docs(self) -> int:
        return self.index.n_docs

    @property
    def n_shards(self) -> int:
        return self.index.n_shards if self.is_sharded else 1

    # ---- plan/execute ----
    def plan(
        self,
        config: WarpSearchConfig = WarpSearchConfig(),
        *,
        dfilter: "df.DocFilter | None" = None,
    ) -> SearchPlan:
        """Validate ``config`` against index geometry + backend capabilities
        and compile the pipeline. Raises ValueError on an unsatisfiable
        config; returns a cached plan for a previously planned config.

        ``dfilter`` restricts retrieval to the filter's surviving doc ids
        (``core/docfilter.py``): the filter is resolved against the index
        geometry once here and threaded through the pipeline as a runtime
        operand — filtered plans are cached per (config, filter digest),
        and two filters with the same survivor set share a plan. Filtered
        top-k doc ids are bit-identical to post-hoc-filtering an
        unfiltered retrieval at inflated k (see the docfilter module for
        the exactness argument)."""
        if dfilter is not None and not isinstance(dfilter, df.DocFilter):
            raise TypeError(
                f"dfilter must be a DocFilter, got {type(dfilter).__name__}"
            )
        key = (config, dfilter.digest if dfilter is not None else None)
        cached = self._plans.get(key)
        if cached is not None:
            return cached
        fctx = self._resolve_filter(dfilter)
        resolved = self._resolve(config)
        self._validate(resolved)
        single, bucket_for = self._compile_single(resolved, fctx)
        batch, batch_at = self._compile_batch(resolved, fctx)

        fallback_factory = None
        if resolved.executor == "kernel":
            def fallback_factory(_self=self, _cfg=resolved, _fctx=fctx):
                # Same resolved pipeline, reference executor: identical
                # candidate sets + summation order -> bit-identical top-k.
                ref_cfg = dataclasses.replace(_cfg, executor="reference")
                fb_single, _ = _self._compile_single(ref_cfg, _fctx)
                fb_batch, fb_batch_at = _self._compile_batch(ref_cfg, _fctx)
                return fb_single, fb_batch, fb_batch_at

        plan = SearchPlan(
            config=resolved,
            n_shards=self.n_shards,
            backend=jax.default_backend(),
            index_geometry=self._geometry(),
            _single=single,
            _batch=batch,
            _index=self.index,
            _bucket_for=bucket_for,
            _batch_at=batch_at,
            _fallback_factory=fallback_factory,
            filter_info=(
                dfilter.describe() if dfilter is not None else None
            ),
        )
        self._plans[key] = plan
        self._plans[(resolved, key[1])] = plan
        return plan

    def plan_for_k(
        self,
        k: int,
        config: WarpSearchConfig | None = None,
        *,
        dfilter: "df.DocFilter | None" = None,
    ) -> SearchPlan:
        """Plan with per-request k-laddered defaults: resolve retrieval
        hyperparameters from the requested result depth (``K_LADDER`` via
        ``laddered_config`` — explicit ``config`` settings still win),
        then plan as usual. The chosen rung is visible as ``k_ladder`` in
        ``describe()``; plans at different rungs carry distinct
        fingerprints."""
        n_tokens = (
            self.index.resolved_n_tokens()
            if self.is_sharded
            else self.index.n_tokens
        )
        cfg = laddered_config(
            k,
            config,
            n_tokens=n_tokens,
            n_centroids=self.index.n_centroids,
        )
        return self.plan(cfg, dfilter=dfilter)

    def retrieve(
        self,
        q: jax.Array,
        qmask: jax.Array | None = None,
        config: WarpSearchConfig = WarpSearchConfig(),
        *,
        dfilter: "df.DocFilter | None" = None,
    ) -> TopKResult:
        """Plan (cached) + single-query dispatch."""
        return self.plan(config, dfilter=dfilter).retrieve(q, qmask)

    def retrieve_batch(
        self,
        q: jax.Array,
        qmask: jax.Array | None = None,
        config: WarpSearchConfig = WarpSearchConfig(),
        *,
        dfilter: "df.DocFilter | None" = None,
    ) -> TopKResult:
        """Plan (cached) + batched dispatch."""
        return self.plan(config, dfilter=dfilter).retrieve_batch(q, qmask)

    def _resolve_filter(self, dfilter):
        """Resolve a ``DocFilter`` against this index's geometry: a local
        ``FilterView``, a stacked per-shard view, or the segmented triple
        (see ``core/docfilter.py``). None passes through."""
        if dfilter is None:
            return None
        if not isinstance(dfilter, df.DocFilter):
            raise TypeError(
                f"dfilter must be a DocFilter, got {type(dfilter).__name__}"
            )
        if dfilter.n_docs != self.n_docs:
            raise ValueError(
                f"DocFilter covers {dfilter.n_docs} docs but the index "
                f"holds {self.n_docs}; rebuild the filter against this "
                "corpus snapshot"
            )
        if self.is_sharded:
            return df.resolve_sharded(dfilter, self.index)
        if self.is_segmented:
            return df.resolve_segmented(dfilter, self.index)
        return df.resolve_local(dfilter, self.index)

    # ---- internals ----
    def _resolve(self, config: WarpSearchConfig) -> WarpSearchConfig:
        if self.is_sharded:
            return dist.resolve_sharded_config(self.index, config)
        if self.is_segmented:
            return self._resolve_segmented(config)
        return engine.resolve_config(self.index, config)

    def _resolve_segmented(self, config: WarpSearchConfig) -> WarpSearchConfig:
        """Segmented analogue of ``engine.resolve_config``: t' from the
        total token count across segments, and the ragged worklist bound
        from the COMBINED per-segment CSR geometries — one flat worklist
        spans base + deltas, so a probed cluster's tile count is the sum
        of its per-segment tile counts (``worklist_bound_segmented``).
        "auto" compares that bound against the dense segmented cost,
        ``nprobe * sum_s cap_s`` slots per query token (each segment pads
        to its own cap on the dense path).
        """
        idx = self.index
        if idx.n_tokens == 0:
            raise ValueError(
                "segmented index has n_tokens == 0 — nothing to retrieve. "
                "Build or load a non-empty index before planning a search."
            )
        config = dataclasses.replace(
            config,
            t_prime=config.resolved_t_prime(idx.n_tokens),
            k_impute=config.resolved_k_impute(idx.n_centroids),
            executor=config.resolved_executor(ops.on_tpu()),
        )
        geo = dict(n_tokens=idx.n_tokens, nbits=idx.nbits, dim=idx.dim)
        if config.layout == "dense":
            config = engine.resolve_tile_fields(
                config, cap=idx.cap, layout="dense", **geo
            )
            if config.worklist_tiles is None and config.worklist_buckets is None:
                return config
            return dataclasses.replace(
                config, worklist_tiles=None, worklist_buckets=None
            )
        ragged = engine.resolve_tile_fields(
            config, cap=idx.cap, layout="ragged", **geo
        )
        tile = ragged.tile_c
        bound = wl.worklist_bound_segmented(
            idx.per_segment_cluster_sizes(), config.nprobe, tile
        )
        dense_slots = config.nprobe * sum(s.cap for s in idx.segments)
        layout = config.layout
        if layout == "auto":
            layout = "ragged" if bound * tile < dense_slots else "dense"
        if layout == "dense":
            config = engine.resolve_tile_fields(
                config, cap=idx.cap, layout="dense", **geo
            )
            return dataclasses.replace(
                config, layout="dense", worklist_tiles=None,
                worklist_buckets=None,
            )
        return dataclasses.replace(
            ragged,
            layout="ragged",
            worklist_tiles=bound,
            worklist_buckets=wl.bucket_ladder(bound),
        )

    def _validate(self, cfg: WarpSearchConfig) -> None:
        idx = self.index
        n_centroids = idx.n_centroids
        problems = []
        if cfg.nprobe < 1:
            problems.append(f"nprobe={cfg.nprobe} must be >= 1")
        if cfg.nprobe > n_centroids:
            problems.append(
                f"nprobe={cfg.nprobe} exceeds the index's "
                f"{n_centroids} centroids"
            )
        if cfg.k < 1:
            problems.append(f"k={cfg.k} must be >= 1")
        # k_impute is clamped to [nprobe, n_centroids] during resolution
        # (resolved_k_impute), so it cannot be invalid here.
        if cfg.t_prime < 1:
            problems.append(f"t_prime={cfg.t_prime} must be >= 1")
        max_cands = cfg.nprobe * idx.cap
        if idx.cap and cfg.k > max_cands:
            problems.append(
                f"k={cfg.k} exceeds the candidate pool nprobe*cap="
                f"{max_cands}; raise nprobe or lower k"
            )
        if problems:
            raise ValueError(
                "unsatisfiable search plan: " + "; ".join(problems)
            )

    def _geometry(self) -> dict:
        idx = self.index
        geo = {
            "n_docs": idx.n_docs,
            "n_centroids": idx.n_centroids,
            "cap": idx.cap,
            "nbits": idx.nbits,
            "dim": idx.dim,
        }
        if self.is_sharded:
            geo["n_tokens"] = idx.resolved_n_tokens()
        else:
            geo["n_tokens"] = idx.n_tokens
        if self.is_segmented:
            geo["n_segments"] = idx.n_segments
        return geo

    @staticmethod
    def _is_adaptive(cfg: WarpSearchConfig) -> bool:
        return (
            cfg.layout == "ragged"
            and cfg.worklist_buckets is not None
            and len(cfg.worklist_buckets) > 1
        )

    def _local_sel_picker(self, cfg: WarpSearchConfig, fview=None):
        """``(sel, qmask) -> smallest ladder rung`` fitting the masked
        probe tile demand of a WARP_SELECT output — the adaptive
        dispatcher's rung choice. With ``fview`` probe runs whose cluster
        holds no surviving tokens count zero tiles (the worklist drops
        them), so a selective filter lowers the chosen rung."""
        buckets = cfg.worklist_buckets
        tile = ops.resolve_tile_c(self.index.cap, cfg.tile_c, layout="ragged")
        # memory="full" builds one flat worklist over all Q query tokens
        # (demand amortizes across tokens); "scan_qtokens" builds one per
        # token, so the bucket must fit the worst single token.
        amortized = cfg.memory == "full"
        live_np = (
            np.asarray(fview.cluster_live, bool) if fview is not None else None
        )

        def pick(sel, qmask):
            # Masked query tokens build no worklist tiles (the engine
            # zeroes their probe sizes — see ``score_candidates``), so
            # demand is computed over active tokens only; otherwise short
            # queries and batch padding rows would inflate the rung.
            m = np.asarray(qmask, bool)
            sizes = np.asarray(sel.probe_sizes)
            if live_np is not None:
                sizes = wl.filtered_probe_sizes(
                    sizes, np.asarray(sel.probe_cids), live_np
                )
            tiles = wl.probe_tile_counts(sizes, tile) * m[..., None]
            needed = wl.needed_worklist_tiles(tiles, amortized=amortized)
            return wl.pick_bucket(buckets, needed)

        return pick

    def _compile_single(self, cfg: WarpSearchConfig, fctx=None):
        """-> (search fn, bucket probe | None) for single-query dispatch."""
        if self._is_adaptive(cfg):
            run, bucket_for, _ = self._adaptive_dispatch(
                cfg, query_batch=False, fctx=fctx
            )
            return run, bucket_for
        return self._static_fn(cfg, query_batch=False, fctx=fctx), None

    def _compile_batch(self, cfg: WarpSearchConfig, fctx=None):
        """-> (batch fn, forced-rung accessor | None)."""
        if self._is_adaptive(cfg):
            # The batch dispatcher picks one bucket covering the whole
            # batch (max demand over batch elements): one program per call.
            run, _, fn_at = self._adaptive_dispatch(
                cfg, query_batch=True, fctx=fctx
            )
            return run, fn_at
        return self._static_fn(cfg, query_batch=True, fctx=fctx), None

    def _static_fn(self, cfg: WarpSearchConfig, *, query_batch: bool, fctx=None):
        if self.is_sharded:
            fn = dist.make_sharded_search_fn(
                self.index, cfg, self.mesh, self.shard_axes,
                query_batch=query_batch, with_filter=fctx is not None,
            )
            if fctx is not None:
                return lambda index, q, qmask: fn(index, q, qmask, fctx)
            return fn
        if self.is_segmented:
            from repro.store.segments import make_segmented_search_fn

            run = make_segmented_search_fn(
                self.index, cfg, query_batch=query_batch,
                with_filter=fctx is not None,
            )
            if fctx is not None:
                return lambda index, q, qmask: run(index, q, qmask, fctx)
            return run
        if query_batch:
            return lambda index, q, qmask: engine._search_many(
                index, q, qmask, cfg, dfilter=fctx
            )
        return lambda index, q, qmask: engine._search_one(
            index, q, qmask, cfg, dfilter=fctx
        )

    def _adaptive_dispatch(
        self, cfg: WarpSearchConfig, *, query_batch: bool, fctx=None
    ):
        """Build the query-adaptive ragged dispatcher.

        Returns (run fn, bucket probe). Per call the probe computes the
        actual worklist tile demand of the selected probe set (host-side,
        from WARP_SELECT probe metadata), picks the smallest ladder rung
        that fits, and runs the pipeline compiled for that rung —
        compilation per rung is lazy and cached, so steady state is one
        cheap stage-1 (or none: the local path reuses its probe output)
        plus one compiled call.

        With ``fctx`` (a resolved filter view) demand counts only probe
        runs whose cluster holds surviving tokens — the same runs the
        filtered worklist keeps — so a selective filter lowers the chosen
        rung, and the compiled pipelines thread the filter operand.
        """
        buckets = cfg.worklist_buckets
        tile = ops.resolve_tile_c(self.index.cap, cfg.tile_c, layout="ragged")
        # memory="full" builds one flat worklist over all Q query tokens
        # (demand amortizes across tokens); "scan_qtokens" builds one per
        # token, so the bucket must fit the worst single token.
        amortized = cfg.memory == "full"
        # The sharded/segmented pre-passes re-run stage 1 in a SEPARATE
        # XLA program from the search body; a last-ulp centroid-score
        # difference could flip a top-nprobe tie and shift the true demand
        # by ~one cluster swap, which amortizes to about one tile over Q.
        # One tile of headroom makes a boundary-straddling rung choice
        # safe; the local path reuses the body's own probe output and
        # needs none.
        PREPASS_SLACK = 1

        def bucket_cfg(b: int) -> WarpSearchConfig:
            return dataclasses.replace(
                cfg, worklist_tiles=b, worklist_buckets=None
            )

        def lazy_fn_at(make_fn):
            """Lazily compile-and-cache one pipeline per forced rung —
            also surfaced as ``SearchPlan.retrieve_batch_at``'s accessor."""
            cache: dict = {}

            def fn_at(b):
                fn = cache.get(b)
                if fn is None:
                    fn = cache[b] = make_fn(b)
                return fn

            return fn_at

        def lazy_bucket_runner(bucket_for, make_fn):
            """Shared dispatch shape of the pre-pass paths: pick the rung,
            lazily compile-and-cache its pipeline, run it."""
            fn_at = lazy_fn_at(make_fn)

            def run(index, q, qmask):
                return fn_at(bucket_for(q, qmask))(index, q, qmask)

            return run, bucket_for, fn_at

        def masked_tiles(tiles, qmask):
            # Masked query tokens build no worklist tiles (the engine
            # zeroes their probe sizes — see ``score_and_reduce``), so
            # demand must be computed over active tokens only; otherwise
            # short queries and batch padding rows would inflate the rung.
            m = np.asarray(qmask, bool)
            return tiles * m[..., None]

        if self.is_sharded:
            shard_live = (
                np.asarray(fctx.cluster_live, bool)
                if fctx is not None
                else None
            )

            def bucket_for(q, qmask):
                # One bucket for all shards (max demand): the shard_map
                # body is a single program and stays unbranched.
                sizes, cids = dist.sharded_probe_sizes(
                    self.index, q, qmask, cfg, query_batch
                )
                sizes = np.asarray(sizes)
                if shard_live is not None:
                    # Per-shard liveness gather: probe runs on clusters
                    # with no surviving tokens build no worklist tiles.
                    cids_np = np.asarray(cids)
                    shard_idx = np.arange(shard_live.shape[0]).reshape(
                        (-1,) + (1,) * (cids_np.ndim - 1)
                    )
                    sizes = np.where(shard_live[shard_idx, cids_np], sizes, 0)
                tiles = masked_tiles(
                    wl.probe_tile_counts(sizes, tile),
                    np.asarray(qmask, bool)[None],  # broadcast over shards
                )
                needed = wl.needed_worklist_tiles(tiles, amortized=amortized)
                return wl.pick_bucket(buckets, needed + PREPASS_SLACK)

            def make_sharded_fn(b):
                fn = dist.make_sharded_search_fn(
                    self.index, bucket_cfg(b), self.mesh, self.shard_axes,
                    query_batch=query_batch, with_filter=fctx is not None,
                )
                if fctx is not None:
                    return lambda index, q, qmask: fn(index, q, qmask, fctx)
                return fn

            return lazy_bucket_runner(bucket_for, make_sharded_fn)

        if self.is_segmented:
            from repro.store.segments import (
                make_segmented_search_fn,
                segmented_probe_cids,
            )

            idx = self.index
            combined_sizes = idx.combined_cluster_sizes()
            # Combined per-cluster tile demand: one flat worklist spans
            # the segments, so a probed cluster costs the SUM of its
            # per-segment tile counts. Filtered plans zero the
            # (segment, cluster) cells with no surviving tokens — those
            # runs never enter the worklist.
            per_seg_tiles = (idx.per_segment_cluster_sizes() + tile - 1) // tile
            if fctx is not None:
                per_seg_tiles = per_seg_tiles * fctx[2]
            cluster_tiles = per_seg_tiles.sum(axis=0)
            centroids = idx.base.centroids

            def bucket_for(q, qmask):
                cids = segmented_probe_cids(
                    centroids, combined_sizes, q, qmask, cfg, query_batch
                )
                # The segmented ragged path always builds the full-Q
                # worklist (no scan_qtokens variant), so demand amortizes.
                tiles = masked_tiles(cluster_tiles[np.asarray(cids)], qmask)
                needed = wl.needed_worklist_tiles(tiles, amortized=True)
                return wl.pick_bucket(buckets, needed + PREPASS_SLACK)

            def make_segmented_fn(b):
                run = make_segmented_search_fn(
                    idx, bucket_cfg(b), query_batch=query_batch,
                    with_filter=fctx is not None,
                )
                if fctx is not None:
                    return lambda index, q, qmask: run(index, q, qmask, fctx)
                return run

            return lazy_bucket_runner(bucket_for, make_segmented_fn)

        # Local path: stage 1 runs ONCE (select_probes), the bucket is
        # read off its probe sizes, and stages 2+3 finish under the
        # bucket's static bound — no duplicated work at all.
        bucket_from_sel = self._local_sel_picker(cfg, fview=fctx)

        def bucket_for(q, qmask):
            sel = engine.select_probes(self.index, q, qmask, cfg, query_batch)
            return bucket_from_sel(sel, qmask)

        def make_fn(b):
            # Forced rung: the same select_probes -> finish_from_probes
            # composition the adaptive run uses, so dispatching at a
            # request's own chosen rung is bit-identical to ``run``.
            fcfg = bucket_cfg(b)

            def fn(index, q, qmask):
                sel = engine.select_probes(index, q, qmask, cfg, query_batch)
                return engine.finish_from_probes(
                    index, q, qmask, sel, fcfg, query_batch, dfilter=fctx
                )

            return fn

        def run(index, q, qmask):
            sel = engine.select_probes(index, q, qmask, cfg, query_batch)
            b = bucket_from_sel(sel, qmask)
            return engine.finish_from_probes(
                index, q, qmask, sel, bucket_cfg(b), query_batch, dfilter=fctx
            )

        return run, bucket_for, lazy_fn_at(make_fn)
