"""Request batcher for the retrieval engine (production serving shape).

WARP's jit'd search has a static query-batch dimension, so the server
collects incoming queries into fixed-size batches dispatched on the
classic deadline rule: a batch goes when it is full OR when its oldest
request has waited ``max_wait_s``. Under-full batches are padded with
masked queries — padding work is bounded by the batch size, and the
paper's own multi-thread scaling argument (Fig. 10) maps onto batching
here: on TPU, intra-query parallelism is the mesh, inter-query
parallelism is the batch.

On top of that deadline core the server composes the serving subsystem:

- **bucket-aware continuous batching** (``serving/scheduler.py``): on
  adaptive ragged plans the admission-time probe pre-pass
  (``SearchPlan.adaptive_bucket``) tags every request with the worklist
  rung it needs, requests queue per rung, and each batch executes at the
  smallest rung its members need (``SearchPlan.retrieve_batch_at``)
  instead of the queue-wide worst case — with age-based promotion as a
  starvation guard. Results are bit-identical to direct retrieval at any
  fitting rung (worklist exactness).
- **two-level cache** (``serving/cache.py``): an encoded-query (rung)
  cache and an LRU result cache, both keyed on (query hash, plan
  fingerprint, index epoch) — a result-cache hit completes the request
  at submit time.
- **admission control + maintenance** (``serving/admission.py``): an
  SLO gate that sheds load with a typed ``Overloaded`` instead of
  queueing unboundedly, and a compaction-trigger policy that runs
  ``store.compact()`` + ``reload()`` from the server loop.
- **multi-index routing + filtered retrieval**: ``add_tenant`` registers
  additional served indexes behind ``submit(tenant=...)`` — each tenant
  gets an independent (index, plan ladder, cache namespace, metrics
  labels) tuple behind the one ``BucketScheduler``; ``submit(dfilter=)``
  pushes a ``DocFilter`` into the pipeline (bit-identical to post-hoc
  filtering, see ``core/docfilter.py``); ``delete_documents`` tombstones
  doc ids — filtered out of every reply immediately, reclaimed at the
  next compaction. Tenant and filter are folded into cache keys and
  batch groups, so no reply, cache entry, or batch ever crosses them.

The server dispatches through the unified ``Retriever`` plan, so it
serves single-device, document-sharded, AND segmented indexes with the
same code. The clock is injectable so tests drive deadline/shedding
behavior deterministically.

Request lifecycle: ``submit`` -> ``poll`` returns the ``PENDING``
sentinel until the request's batch has been dispatched (or returns
immediately after a cache hit), then pops and returns the
``(scores, doc_ids)`` pair exactly once; polling an id that was already
popped raises ``ResultAlreadyTaken`` (a ``KeyError`` subclass), an id
that was never submitted a plain ``KeyError`` — client retry logic can
tell a double-read from a lost id. ``result`` is the blocking
convenience wrapper that drives the server loop until the request
completes.

``reload`` hot-swaps the served index (e.g. after ``repro.store.compact``
folded delta segments into a fresh base): the new plan is compiled from
the originally *requested* config — data-dependent resolutions like t'
re-materialize against the new geometry — queued requests re-home onto
the new plan's rung ladder and dispatch on their next ``step``, and the
index epoch bump invalidates every cache entry from the old index;
nothing is dropped, nothing stale is served.

Resilience semantics (every failure is a *typed* error or a *metered*
degradation, never a silent wrong answer):

- **deadlines**: ``submit(..., deadline_s=)`` attaches a per-request
  deadline; a request still queued when it expires is shed *pre-dispatch*
  (it never occupies a batch slot) and its ``poll`` raises
  ``DeadlineExceeded`` exactly once (``serving_deadline_shed_total``).
- **validate-then-swap reload**: everything that can fail — store load,
  plan compilation, kernel warmup — runs before any server state is
  mutated, so a failed ``reload`` leaves epoch, caches, and the queued
  backlog exactly as they were. Store-path reloads quarantine corrupt
  delta segments (``load_index(quarantine_segments=True)``) instead of
  refusing to serve.
- **maintenance backoff**: a failed ``maintain`` tick rolls the on-disk
  swap protocol back (``recover_interrupted_compact``), keeps serving
  the old epoch, and retries after exponential backoff
  (``CompactionPolicy.retry_backoff_s``,
  ``serving_maintain_retries_total``).
- **health()**: ``ok | degraded | overloaded`` plus concrete reasons
  (quarantined segments, executor fallback, failing maintenance), also
  exported as the ``serving_health_status`` gauge (0/1/2).
"""

from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro import fault, obs
from repro.core import Retriever, WarpSearchConfig
from repro.core.distributed import ShardedWarpIndex
from repro.core.docfilter import DocFilter
from repro.core.types import WarpIndex
from repro.serving.admission import (
    AdmissionGate,
    AdmissionPolicy,
    CompactionPolicy,
    DeadlineExceeded,
)
from repro.serving.cache import LRUCache, query_key
from repro.serving.scheduler import BatchPolicy, BucketScheduler

__all__ = [
    "BatchPolicy",
    "RetrievalServer",
    "ResultAlreadyTaken",
    "PENDING",
]


class _PendingType:
    """Sentinel: the request is known but its batch has not run yet."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:
        return "PENDING"

    def __bool__(self) -> bool:
        return False


PENDING = _PendingType()


class ResultAlreadyTaken(KeyError):
    """The request completed and its result was already popped by a
    previous ``poll``/``result`` call — results are delivered exactly
    once. Subclasses ``KeyError`` so pre-existing handlers keep working;
    distinct from the plain ``KeyError`` raised for never-submitted ids."""


@dataclasses.dataclass
class _Pending:
    req_id: int
    q: np.ndarray
    qmask: np.ndarray
    arrival: float
    qkey: str | None = None  # content hash (None with caching disabled)
    deadline: float | None = None  # absolute, on the server clock
    tenant: str | None = None  # routing handle (None = default index)
    dfilter: DocFilter | None = None  # request filter, pre-tombstone merge
    plan: object | None = None  # resolved (possibly filtered) SearchPlan
    fp: str | None = None  # that plan's fingerprint (cache-key component)
    group: tuple | None = None  # scheduler batch-homogeneity key


@dataclasses.dataclass
class _Tenant:
    """Per-index serving state behind one ``tenant=`` routing handle.

    The server keeps one record per served index — the default tenant
    (key ``None``, the index the server was constructed with) plus any
    ``add_tenant`` extras — each with its own retriever, plan ladder,
    cache namespace (tenant + filter digest are folded into every cache
    key), and metrics labels, all multiplexed behind the one
    ``BucketScheduler``.

    ``deleted`` / ``tomb`` are the tombstone view: doc ids removed by
    ``delete_documents`` keep occupying the index until the next
    compaction, but every request against this tenant is intersected
    with the ``DocFilter.tombstones`` view so they can never appear in a
    reply. A reload from a store path re-reads ``tombstones.json`` (a
    post-compact store carries none, closing the lifecycle).
    """

    name: str | None = None
    retriever: Retriever | None = None
    requested_config: WarpSearchConfig | None = None
    plan: object | None = None  # base (unfiltered) SearchPlan
    config: WarpSearchConfig | None = None  # the plan's resolved config
    fingerprint: str | None = None
    store_path: str | None = None
    quarantined: tuple = ()
    deleted: frozenset = dataclasses.field(default_factory=frozenset)
    tomb: DocFilter | None = None  # DocFilter.tombstones over ``deleted``


def _default_tenant_field(field: str):
    """Legacy single-index attribute (``server.retriever`` & co.) as a
    read/write view onto the default tenant's record."""

    def _get(self):
        return getattr(self._tenants[None], field)

    def _set(self, value):
        setattr(self._tenants[None], field, value)

    return property(_get, _set)


class RetrievalServer:
    def __init__(
        self,
        index: WarpIndex | ShardedWarpIndex | Retriever,
        config: WarpSearchConfig = WarpSearchConfig(),
        policy: BatchPolicy = BatchPolicy(),
        clock: Callable[[], float] = time.monotonic,
        *,
        bucket_aware: bool = True,
        cache_size: int = 256,
        admission: AdmissionPolicy | AdmissionGate | None = None,
        compaction: CompactionPolicy | None = None,
        store_path: str | None = None,
        registry: obs.MetricsRegistry | None = None,
        sleep: Callable[[float], None] | None = None,
    ):
        # Serving counters live in a metrics registry — private per server
        # by default so two servers (or two tests) never share counts;
        # launch/serve.py passes the process registry for exposition.
        self.metrics = registry if registry is not None else obs.MetricsRegistry()
        # All per-index serving state lives in per-tenant records: the
        # default tenant (key None) is the index this server was built
        # with; ``add_tenant`` registers more. The legacy single-index
        # attributes (``retriever``/``plan``/``config``/...) are property
        # views onto the default record, so existing callers are
        # untouched.
        self._tenants: dict = {None: _Tenant()}
        self._tenant_c: dict = {}
        self.retriever = (
            index if isinstance(index, Retriever) else Retriever.from_index(index)
        )
        # Keep the pre-resolution config: a reload must re-resolve t' /
        # k_impute / executor against the NEW index, not freeze the old.
        self._requested_config = config
        self.plan = self.retriever.plan(config)
        # Surface kernel-path failures now (demoting to the bit-identical
        # reference executor) instead of on the first live request.
        self.plan.warmup()
        self.config = self.plan.config
        self.policy = policy
        self.clock = clock
        # ``result`` parks on this between deadline checks. A real sleep
        # against an injected fake clock would deadlock (wall time passes,
        # the fake clock doesn't), so it only defaults on when the clock
        # is the real one; tests with fake clocks keep the force-dispatch
        # driver unless they inject their own sleep.
        if sleep is None and clock is time.monotonic:
            sleep = time.sleep
        self._sleep = sleep
        self.bucket_aware = bucket_aware
        self.index_epoch = 0
        self._fingerprint = self.plan.fingerprint()
        if isinstance(admission, AdmissionPolicy):
            admission = AdmissionGate(admission, clock, registry=self.metrics)
        self.admission = admission
        self.compaction = compaction
        self.store_path = store_path
        self._last_compact = -float("inf")
        self._maintain_failures = 0
        self._maintain_error: str | None = None
        self._maintain_backoff_until = -float("inf")
        self._quarantined: tuple[str, ...] = tuple(
            getattr(self.retriever.index, "quarantined", ()) or ()
        )
        if cache_size:
            self.result_cache: LRUCache | None = LRUCache(
                cache_size, registry=self.metrics, name="result"
            )
            self._rung_cache: LRUCache | None = LRUCache(
                cache_size, registry=self.metrics, name="rung"
            )
        else:
            self.result_cache = self._rung_cache = None
        self.scheduler = self._make_scheduler()
        self._inflight: set[int] = set()
        self._results: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        # Typed failure outcomes (e.g. DeadlineExceeded), delivered by
        # ``poll`` exactly once like any result.
        self._errors: dict[int, Exception] = {}
        self._next_id = 0
        # Legacy ``stats`` keys -> registry counters; the ``stats``
        # property reconstructs the historical dict view from these.
        self._c = {
            "batches": self.metrics.counter(
                "serving_batches_total", "Batches dispatched"
            ),
            "padded_slots": self.metrics.counter(
                "serving_padded_slots_total",
                "Masked padding slots in under-full batches",
            ),
            "served": self.metrics.counter(
                "serving_requests_served_total", "Requests completed"
            ),
            "reloads": self.metrics.counter(
                "serving_reloads_total", "Hot index swaps"
            ),
            "cache_hits": self.metrics.counter(
                "serving_submit_cache_hits_total",
                "Requests completed at submit time by the result cache",
            ),
            "compactions": self.metrics.counter(
                "serving_compactions_total",
                "Store compactions run by maintain()",
            ),
            "deadline_shed": self.metrics.counter(
                "serving_deadline_shed_total",
                "Queued requests shed pre-dispatch at their deadline",
            ),
            "maintain_retries": self.metrics.counter(
                "serving_maintain_retries_total",
                "Failed maintain() ticks rolled back and scheduled for retry",
            ),
        }
        self._c_step_host = self.metrics.counter(
            "serving_step_host_seconds_total",
            "Host time inside step() outside serve.await, on the server clock",
        )
        self._g_health = self.metrics.gauge(
            "serving_health_status",
            "health() status: 0=ok, 1=degraded, 2=overloaded",
        )
        self._h_dispatch = self.metrics.histogram(
            "serving_dispatch_seconds",
            "Batch dispatch latency (retrieve + result distribution)",
        )
        self._g_epoch = self.metrics.gauge(
            "serving_index_epoch", "Current served index epoch"
        )

    # ---- default-tenant views (legacy single-index attribute API) ----
    retriever = _default_tenant_field("retriever")
    plan = _default_tenant_field("plan")
    config = _default_tenant_field("config")
    store_path = _default_tenant_field("store_path")
    _requested_config = _default_tenant_field("requested_config")
    _fingerprint = _default_tenant_field("fingerprint")
    _quarantined = _default_tenant_field("quarantined")

    @property
    def stats(self) -> dict:
        """Integer counters reconstructed from the registry: the legacy
        dict (batches/padded_slots/served/reloads/cache_hits/compactions/
        deadline_shed/maintain_retries) plus two times in microseconds on
        the server clock: ``queue_wait_us``, submit-to-dispatch wait
        summed over dispatched requests (the scheduler's
        ``serving_queue_wait_seconds``), and ``step_host_us``, host time
        inside ``step`` outside the wait for the device
        (``serving_step_host_seconds_total``)."""
        out = {k: int(c.value) for k, c in self._c.items()}
        out["queue_wait_us"] = round(self.scheduler.queue_wait_seconds() * 1e6)
        out["step_host_us"] = round(self._c_step_host.value * 1e6)
        return out

    # ---- multi-tenant routing ----
    def _state(self, tenant) -> _Tenant:
        try:
            return self._tenants[tenant]
        except KeyError:
            known = sorted(t for t in self._tenants if t is not None)
            raise KeyError(
                f"unknown tenant {tenant!r} (registered: {known or 'none'}; "
                f"None is the default index)"
            ) from None

    def _tenant_counters(self, tenant) -> dict:
        lab = "default" if tenant is None else tenant
        tc = self._tenant_c.get(lab)
        if tc is None:
            tc = self._tenant_c[lab] = {
                "submitted": self.metrics.counter(
                    "serving_tenant_submitted_total",
                    "Requests admitted for this tenant", tenant=lab,
                ),
                "served": self.metrics.counter(
                    "serving_tenant_served_total",
                    "Requests completed for this tenant", tenant=lab,
                ),
                "cache_hits": self.metrics.counter(
                    "serving_tenant_cache_hits_total",
                    "Submit-time result-cache hits for this tenant",
                    tenant=lab,
                ),
            }
        return tc

    @staticmethod
    def _effective_filter(state: _Tenant, dfilter):
        """The filter a request actually runs under: the request's own
        ``dfilter`` intersected with the tenant's tombstone view (deleted
        docs must stay invisible no matter what the caller asked for)."""
        if dfilter is not None and not isinstance(dfilter, DocFilter):
            raise TypeError(
                f"dfilter must be a DocFilter, got {type(dfilter).__name__}"
            )
        if dfilter is None:
            return state.tomb
        if state.tomb is None:
            return dfilter
        return dfilter.intersect(state.tomb)

    def _plan_for(self, state: _Tenant, dfilter):
        """-> ``(plan, fingerprint, effective_filter)`` for one request.

        Unfiltered requests reuse the tenant's pre-warmed base plan;
        filtered ones go through ``Retriever.plan(dfilter=)``, which
        caches per (config, filter digest) — repeat filters compile
        once."""
        eff = self._effective_filter(state, dfilter)
        if eff is None:
            return state.plan, state.fingerprint, None
        plan = state.retriever.plan(state.requested_config, dfilter=eff)
        return plan, plan.fingerprint(), eff

    @staticmethod
    def _group_for(tenant, eff) -> tuple | None:
        """Scheduler batch-homogeneity key: None for the default tenant
        unfiltered (exact legacy scheduling), else (tenant, filter
        digest) — a batch executes one plan against one index, so
        tenant and filter must match across its members."""
        if tenant is None and eff is None:
            return None
        return (tenant, eff.digest if eff is not None else None)

    def _build_state(self, name, index, requested: WarpSearchConfig) -> _Tenant:
        """Load/plan/warm one tenant's index — everything that can fail
        runs here, before any server state is touched."""
        store_path = None
        if isinstance(index, (str, os.PathLike)):
            from repro.store import load_index  # deferred: store dep on core

            store_path = os.fspath(index)
            index = load_index(store_path, quarantine_segments=True)
        retriever = (
            index if isinstance(index, Retriever) else Retriever.from_index(index)
        )
        plan = retriever.plan(requested)
        plan.warmup()
        deleted = frozenset()
        if store_path is not None:
            from repro.store import read_tombstones

            deleted = frozenset(read_tombstones(store_path))
        return _Tenant(
            name=name,
            retriever=retriever,
            requested_config=requested,
            plan=plan,
            config=plan.config,
            fingerprint=plan.fingerprint(),
            store_path=store_path,
            quarantined=tuple(
                getattr(retriever.index, "quarantined", ()) or ()
            ),
            deleted=deleted,
            tomb=(
                DocFilter.tombstones(sorted(deleted), retriever.n_docs)
                if deleted
                else None
            ),
        )

    def add_tenant(
        self,
        name: str,
        index,
        config: WarpSearchConfig | None = None,
    ) -> None:
        """Register a second (third, ...) served index under ``name``.

        ``index`` accepts everything the constructor does plus a store
        path. The tenant gets its own plan ladder (``config`` defaults to
        the server's requested config), its own cache namespace (tenant +
        filter are folded into every cache key), and its own metrics
        labels — all behind the one scheduler, so cross-tenant deadline
        fairness is most-overdue-first. Validate-then-swap: a failing
        load/plan/warmup raises and registers nothing.
        """
        if not isinstance(name, str) or not name:
            raise TypeError(
                f"tenant name must be a non-empty string, got {name!r}"
            )
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} already registered")
        requested = config if config is not None else self._requested_config
        self._tenants[name] = self._build_state(name, index, requested)
        self._tenant_counters(name)

    @property
    def tenants(self) -> tuple:
        """Registered tenant handles (the default index is ``None``)."""
        return tuple(sorted(
            self._tenants, key=lambda t: ("" if t is None else "\x01" + t)
        ))

    def delete_documents(self, doc_ids, *, tenant=None) -> tuple:
        """Tombstone ``doc_ids`` on ``tenant`` — visible immediately,
        reclaimed at the next compaction.

        Store-backed tenants persist the tombstones (``repro.store.
        delete_documents``) so ``compact()`` drops the rows and the
        post-compact reload clears the in-memory view; pure in-memory
        tenants keep the view until the next ``reload``. Three things
        make deletes immediate despite the rows still being resident:
        the tenant's tombstone filter joins every subsequent request,
        the epoch bump purges every cached result that might contain a
        deleted id, and queued requests are re-homed under the new
        filter so even pre-delete submissions can't resurface one.
        Returns the tenant's full tombstone set."""
        st = self._state(tenant)
        ids = {int(i) for i in np.asarray(list(doc_ids), dtype=np.int64).ravel()}
        if st.store_path is not None:
            from repro.store import delete_documents as store_delete

            st.deleted = frozenset(store_delete(st.store_path, sorted(ids)))
        else:
            st.deleted = frozenset(st.deleted | ids)
        st.tomb = (
            DocFilter.tombstones(sorted(st.deleted), st.retriever.n_docs)
            if st.deleted
            else None
        )
        self.metrics.counter(
            "serving_tenant_deletes_total",
            "delete_documents calls for this tenant",
            tenant="default" if tenant is None else tenant,
        ).inc()
        # Cached results (and rungs) may reference now-deleted ids;
        # epoch-bump them out rather than enumerating.
        self.index_epoch += 1
        self._g_epoch.set(self.index_epoch)
        if self.result_cache is not None:
            self.result_cache.purge_epochs_below(self.index_epoch)
            self._rung_cache.purge_epochs_below(self.index_epoch)
        self._rehome()
        obs.tracer().instant(
            "serve.delete_documents",
            tenant="default" if tenant is None else tenant,
            tombstones=len(st.deleted),
        )
        return tuple(sorted(st.deleted))

    def _make_scheduler(self) -> BucketScheduler:
        """One FIFO per ladder rung on bucket-aware adaptive plans; a
        single queue (the classic deadline batcher) otherwise."""
        rungs = None
        if self.bucket_aware and self._is_adaptive():
            rungs = self.config.worklist_buckets
        return BucketScheduler(
            self.policy, self.clock, rungs=rungs, registry=self.metrics
        )

    def _is_adaptive(self) -> bool:
        return (
            self.config.layout == "ragged"
            and self.config.worklist_buckets is not None
            and len(self.config.worklist_buckets) > 1
        )

    def _cache_key(self, qkey: str, fp: str | None = None) -> tuple:
        # The epoch stays the trailing element — purge_epochs_below
        # keys off k[-1].
        return (qkey, fp if fp is not None else self._fingerprint,
                self.index_epoch)

    def _rung_for(self, q, qmask, qkey: str | None, *, plan=None, fp=None):
        """Admission-time probe pre-pass (level-1 cached): the worklist
        rung this query needs on ``plan`` (default: the default tenant's
        base plan), or None off the bucket-aware path."""
        if plan is None:
            plan = self.plan
        cfg = plan.config
        adaptive = (
            cfg.layout == "ragged"
            and cfg.worklist_buckets is not None
            and len(cfg.worklist_buckets) > 1
        )
        if not (self.bucket_aware and adaptive):
            return None
        if self._rung_cache is not None and qkey is not None:
            key = self._cache_key(qkey, fp)
            hit = self._rung_cache.get(key)
            if hit is not None:
                return hit[0]
            rung = plan.adaptive_bucket(q, qmask)
            # Tupled so a legitimately-None rung is distinguishable from
            # a cache miss.
            self._rung_cache.put(key, (rung,))
            return rung
        return plan.adaptive_bucket(q, qmask)

    # ---- client API ----
    def submit(
        self,
        q: np.ndarray,
        qmask: np.ndarray | None = None,
        *,
        deadline_s: float | None = None,
        tenant: str | None = None,
        dfilter: DocFilter | None = None,
    ) -> int:
        """Admit one query; returns its request id.

        Raises ``Overloaded`` (nothing enqueued, no id burned) when the
        admission gate sheds. A result-cache hit completes the request
        immediately — ``poll`` returns its pair on the first call.

        ``deadline_s`` attaches a queueing deadline (seconds from now on
        the server clock): a request still queued when it expires is shed
        pre-dispatch and its ``poll`` raises ``DeadlineExceeded``.

        ``tenant`` routes to a registered index (``add_tenant``; None =
        the default). ``dfilter`` restricts retrieval to the filter's
        surviving doc ids, in-pipeline and bit-identical to post-hoc
        filtering (``core/docfilter.py``); it is intersected with the
        tenant's tombstone view, and both tenant and filter are folded
        into the cache key and the scheduler's batch group, so requests
        under different filters or tenants never share a cache entry or
        a batch.
        """
        if qmask is None:
            qmask = np.ones(q.shape[:-1], bool)
        # Spans carry the request id; the gate runs before the id is
        # taken, so its span names the id the request gets if admitted.
        with obs.span("serve.submit", queue_depth=len(self.scheduler)) as sp:
            if self.admission is not None:
                with obs.span("serve.admission", rid=self._next_id):
                    self.admission.check(len(self.scheduler))
            # Resolve routing before burning an id: unknown tenant /
            # mis-sized filter raises with nothing enqueued.
            state = self._state(tenant)
            plan, fp, eff = self._plan_for(state, dfilter)
            qkey = (
                query_key(q, qmask, dfilter=eff, tenant=tenant)
                if self.result_cache is not None
                else None
            )
            rid = self._next_id
            self._next_id += 1
            sp.set(rid=rid, tenant="default" if tenant is None else tenant)
            tc = self._tenant_counters(tenant)
            tc["submitted"].inc()
            if qkey is not None:
                hit = self.result_cache.get(self._cache_key(qkey, fp))
                if hit is not None:
                    self._results[rid] = hit
                    self._c["cache_hits"].inc()
                    self._c["served"].inc()
                    tc["cache_hits"].inc()
                    tc["served"].inc()
                    sp.set(cache_hit=True)
                    return rid
            with obs.span("serve.rung_prepass", rid=rid) as rp:
                rung = self._rung_for(q, qmask, qkey, plan=plan, fp=fp)
                rp.set(rung=rung)
            now = self.clock()
            deadline = None if deadline_s is None else now + deadline_s
            group = self._group_for(tenant, eff)
            self.scheduler.push(
                _Pending(
                    rid, q, qmask, now, qkey, deadline,
                    tenant=tenant, dfilter=dfilter,
                    plan=plan, fp=fp, group=group,
                ),
                rung,
                group=group,
            )
            self._inflight.add(rid)
            return rid

    def poll(self, req_id: int):
        """Non-blocking result check.

        Completed -> pops and returns ``(scores, doc_ids)`` (exactly
        once). Shed (deadline) -> pops and raises its typed error
        (``DeadlineExceeded``), also exactly once. Submitted but not yet
        served -> the ``PENDING`` sentinel. Already-popped id ->
        ``ResultAlreadyTaken`` (a ``KeyError``); never-submitted id ->
        plain ``KeyError``.
        """
        if req_id in self._results:
            return self._results.pop(req_id)
        if req_id in self._errors:
            raise self._errors.pop(req_id)
        if req_id in self._inflight:
            return PENDING
        if 0 <= req_id < self._next_id:
            raise ResultAlreadyTaken(
                f"result for request id {req_id} was already retrieved "
                f"(results pop exactly once)"
            )
        raise KeyError(f"request id {req_id} was never submitted")

    def result(self, req_id: int, timeout: float | None = None):
        """Blocking helper: drive the server loop until ``req_id`` completes.

        On the real clock this *parks* between deadline checks — it
        sleeps until the next batch deadline (capped at
        ``policy.max_wait_s`` and the remaining timeout) instead of
        busy-spinning, so a blocking waiter costs no CPU. With an
        injected fake clock (no usable sleep) it forces a padded dispatch
        instead — this is the single-threaded driver, so nobody else
        will. Raises ``TimeoutError`` if ``timeout`` (measured on the
        injected clock) elapses first; the request stays queued and
        poll-able — a timed-out wait is not a cancelled request. Raises
        ``KeyError`` on unknown ids, ``DeadlineExceeded`` if the request
        was shed at its deadline.
        """
        start = self.clock()
        while True:
            out = self.poll(req_id)
            if out is not PENDING:
                return out
            if timeout is not None and self.clock() - start >= timeout:
                raise TimeoutError(
                    f"request {req_id} not served within {timeout}s "
                    f"(still queued; poll() can retrieve it later)"
                )
            if self.step() > 0:
                continue
            nd = self.next_deadline()
            now = self.clock()
            if self._sleep is not None and nd is not None and nd > now:
                wait = min(nd - now, self.policy.max_wait_s)
                if timeout is not None:
                    wait = min(wait, max(start + timeout - now, 0.0))
                if wait > 0.0:
                    self._sleep(wait)
                    continue
            self.step(force=True)

    # ---- lifecycle ----
    def _rehome(self) -> None:
        """Drain the scheduler and re-admit every queued request against
        the *current* tenant states: rung (old ladder/geometry), qkey
        (old filter digest), and group are all stale after a reload or a
        delete. A request whose filter no longer fits its tenant's index
        (e.g. a reload changed the corpus size) gets its error delivered
        typed via ``poll`` instead of poisoning the queue."""
        pending = []
        old_sched = self.scheduler
        while len(old_sched):
            got = old_sched.next_batch(force=True)
            if got is None:
                break
            pending.extend(got[1])
        self.scheduler = self._make_scheduler()
        for p in sorted(pending, key=lambda p: p.arrival):
            self._readmit(p)

    def _readmit(self, p: _Pending) -> None:
        state = self._tenants.get(p.tenant)
        err = None
        if state is None:
            err = KeyError(
                f"tenant {p.tenant!r} was removed while request "
                f"{p.req_id} was queued"
            )
        else:
            try:
                p.plan, p.fp, eff = self._plan_for(state, p.dfilter)
            except (TypeError, ValueError) as e:
                err = e
        if err is not None:
            self._errors[p.req_id] = err
            self._inflight.discard(p.req_id)
            return
        p.qkey = (
            query_key(p.q, p.qmask, dfilter=eff, tenant=p.tenant)
            if self.result_cache is not None
            else None
        )
        p.group = self._group_for(p.tenant, eff)
        rung = self._rung_for(p.q, p.qmask, p.qkey, plan=p.plan, fp=p.fp)
        self.scheduler.push(p, rung, group=p.group)

    def reload(
        self,
        index,
        *,
        config: WarpSearchConfig | None = None,
        tenant: str | None = None,
    ) -> None:
        """Hot-swap the served index without downtime.

        ``index`` may be a ``WarpIndex`` / ``ShardedWarpIndex`` /
        ``SegmentedWarpIndex``, a pre-built ``Retriever``, or a path to a
        store directory (``repro.store``), which is mmap-loaded — the
        zero-copy path a post-``compact()`` pickup wants. The new plan is
        compiled *before* the swap, so in-flight ``submit``/``poll``
        callers never observe a half-reloaded server; queued requests are
        preserved — re-homed onto the new plan's rung ladder (an old
        ladder's rung could truncate against new geometry) — and dispatch
        through the new plan on their next ``step``. The index epoch bump
        invalidates every cache entry keyed against the old index.

        Validate-then-swap: everything that can fail — the store load,
        plan compilation, kernel warmup — runs *before* any server state
        is mutated. A failed reload raises (``StoreCorruption``,
        ``ValueError``, ...) and leaves the server exactly as it was:
        same epoch, same caches, same backlog, still serving. Store-path
        reloads quarantine corrupt delta segments rather than failing
        outright; ``health()`` reports them.

        ``tenant`` reloads a registered tenant's index instead of the
        default. Any reload re-reads the store's tombstones (a
        post-compact store carries none, so the tombstone view clears)
        and re-homes *all* queued requests — their rungs, cache keys and
        batch groups were resolved against pre-reload state.
        """
        t0 = time.perf_counter()
        if fault.FAULTS.plan is not None:
            fault.FAULTS.plan.check("server.reload", index=str(index)[:120])
        if tenant is not None:
            old_state = self._state(tenant)
            requested = (
                config if config is not None else old_state.requested_config
            )
            state = self._build_state(tenant, index, requested)
            # ---- commit point: nothing below raises ----
            self._tenants[tenant] = state
        else:
            requested = config if config is not None else self._requested_config
            old = self.retriever
            new_store_path = self.store_path
            if isinstance(index, (str, os.PathLike)):
                from repro.store import load_index  # deferred: store dep on core

                new_store_path = os.fspath(index)
                index = load_index(new_store_path, quarantine_segments=True)
            if isinstance(index, Retriever):
                retriever = index
            else:
                # Preserve the serving topology: a sharded reload reuses
                # the current mesh/shard_axes rather than a default 1-D
                # mesh; a reload onto a single-device index drops them.
                sharded = isinstance(index, ShardedWarpIndex)
                retriever = Retriever.from_index(
                    index,
                    mesh=old.mesh if sharded else None,
                    shard_axes=old.shard_axes if sharded else ("data",),
                )
            plan = retriever.plan(requested)
            plan.warmup()
            # Disk is the source of truth for tombstones on store-backed
            # reloads: a post-compact store carries none (deletes were
            # reclaimed), a pre-compact one re-yields the persisted set.
            deleted = frozenset()
            if new_store_path is not None:
                from repro.store import read_tombstones

                deleted = frozenset(read_tombstones(new_store_path))
            # ---- commit point: nothing below raises ----
            self._requested_config = requested
            self.store_path = new_store_path
            self._quarantined = tuple(
                getattr(retriever.index, "quarantined", ()) or ()
            )
            self.retriever = retriever
            self.plan = plan
            self.config = plan.config
            self._fingerprint = plan.fingerprint()
            st = self._tenants[None]
            st.deleted = deleted
            st.tomb = (
                DocFilter.tombstones(sorted(deleted), retriever.n_docs)
                if deleted
                else None
            )
        self.index_epoch += 1
        if self.result_cache is not None:
            self.result_cache.purge_epochs_below(self.index_epoch)
            self._rung_cache.purge_epochs_below(self.index_epoch)
        # Re-home queued requests: their rungs, cache keys and groups
        # were resolved against the old plans' ladders and filters.
        self._rehome()
        self._c["reloads"].inc()
        self._g_epoch.set(self.index_epoch)
        self.metrics.histogram(
            "serving_reload_seconds", "Hot index swap duration"
        ).observe(time.perf_counter() - t0)
        obs.tracer().instant("serve.reload", epoch=self.index_epoch)

    def maintain(self) -> bool:
        """One background-maintenance tick: compact + reload when the
        compaction policy's delta thresholds are crossed (at most once
        per ``min_interval_s``). Returns True when a compaction ran;
        call it from the serving loop between batches.

        A failed tick (compaction or the follow-up reload raised) never
        takes the server down: the on-disk swap protocol is rolled back
        to a consistent state via ``recover_interrupted_compact``, the
        old epoch keeps serving, and the next attempt waits out an
        exponential backoff (``CompactionPolicy.retry_backoff_s`` ..
        ``retry_backoff_max_s``)."""
        if self.compaction is None or self.store_path is None:
            return False
        now = self.clock()
        if now < self._maintain_backoff_until:
            return False
        if now - self._last_compact < self.compaction.min_interval_s:
            return False
        from repro.store import (  # deferred: store dep on core
            compact,
            delta_stats,
            recover_interrupted_compact,
        )

        try:
            if not self.compaction.should_compact(delta_stats(self.store_path)):
                return False
            with obs.span("serve.compaction", store=self.store_path):
                compact(self.store_path)
                self._last_compact = self.clock()
                self.reload(self.store_path)
        except Exception as e:
            try:
                recover_interrupted_compact(self.store_path)
            except Exception:
                pass  # recovery is best-effort; old store is untouched
            self._maintain_failures += 1
            self._maintain_error = repr(e)
            backoff = min(
                self.compaction.retry_backoff_s
                * 2 ** (self._maintain_failures - 1),
                self.compaction.retry_backoff_max_s,
            )
            self._maintain_backoff_until = now + backoff
            self._c["maintain_retries"].inc()
            warnings.warn(
                f"maintain() failed ({e!r}); still serving epoch "
                f"{self.index_epoch}, retrying in {backoff:g}s",
                RuntimeWarning,
                stacklevel=2,
            )
            return False
        self._maintain_failures = 0
        self._maintain_error = None
        self._maintain_backoff_until = -float("inf")
        self._c["compactions"].inc()
        return True

    # ---- server loop ----
    def next_deadline(self) -> float | None:
        """Earliest queued-batch deadline (None when idle) — open-loop
        drivers advance their clock to this between arrivals."""
        return self.scheduler.next_deadline()

    def _reap_expired(self) -> int:
        """Shed queued requests whose deadline has passed — pre-dispatch,
        so an expired request never occupies a batch slot or pays for
        retrieval nobody will read. Each shed id gets a typed
        ``DeadlineExceeded`` delivered by its next ``poll``."""
        now = self.clock()
        expired = self.scheduler.reap(
            lambda p: p.deadline is not None and now >= p.deadline
        )
        for p in expired:
            self._errors[p.req_id] = DeadlineExceeded(
                f"request {p.req_id} queued past its deadline "
                f"(waited {max(now - p.arrival, 0.0):.4f}s); "
                f"shed before dispatch"
            )
            self._inflight.discard(p.req_id)
        if expired:
            self._c["deadline_shed"].inc(len(expired))
        return len(expired)

    def step(self, *, force: bool = False) -> int:
        """Dispatch at most one batch; returns number of requests served.

        Spans (``obs.span``; each carries the batch's request ids):
        ``serve.step`` around the whole call (an empty step too), and
        inside it ``serve.assemble`` (pack the queries, move them to the
        device), ``serve.dispatch`` (the plan call, which only enqueues),
        ``serve.await`` (the device-to-host copy of the result, which
        blocks until the device is done) and ``serve.reply`` (fan-out and
        cache fills). Host time inside ``step`` outside ``serve.await``
        accrues to ``serving_step_host_seconds_total``."""
        t_enter = self.clock()
        with obs.span("serve.step") as step_span:
            self._reap_expired()
            got = self.scheduler.next_batch(force=force)
            if got is None:
                self._c_step_host.inc(self.clock() - t_enter)
                return 0
            rung, batch = got
            rids = [p.req_id for p in batch]
            # Every member shares the batch group (tenant + filter), so
            # the head's resolved plan serves the whole batch; legacy
            # pendings (pre-multi-tenant pickles/tests) fall back to the
            # default plan.
            plan = batch[0].plan if batch[0].plan is not None else self.plan
            tenant = batch[0].tenant
            step_span.set(
                rung="none" if rung is None else rung,
                tenant="default" if tenant is None else tenant,
                batch_size=len(batch), rids=rids,
            )
            tr = obs.STATE.tracer
            if tr is not None:
                # Retroactive queue-wait rows: the wait is measured on the
                # server clock (same clock as ``arrival``) but anchored so
                # the interval *ends now* on the tracer's clock — the two
                # clocks may have different epochs. ``tid=request id``
                # gives each request its own Perfetto row.
                now_srv, now_tr = self.clock(), tr.clock()
                for p in batch:
                    wait = max(now_srv - p.arrival, 0.0)
                    tr.add_event(
                        "serve.queue_wait", now_tr - wait, wait,
                        tid=p.req_id, rung="none" if rung is None else rung,
                    )
            t0 = time.perf_counter()
            with obs.span("serve.assemble", rids=rids):
                b = self.policy.max_batch
                qm, d = batch[0].q.shape
                q = np.zeros((b, qm, d), np.float32)
                mask = np.zeros((b, qm), bool)
                for i, p in enumerate(batch):
                    q[i] = p.q
                    mask[i] = p.qmask
                qd, md = jnp.asarray(q), jnp.asarray(mask)
            with obs.span("serve.dispatch", rids=rids):
                if rung is None:
                    res = plan.retrieve_batch(qd, md)
                else:
                    # The batch executes at its rung — every member (and
                    # each backfilled lower-rung rider) fits it, and
                    # padding rows are fully masked so they add no
                    # worklist demand.
                    res = plan.retrieve_batch_at(qd, md, bucket=rung)
            with obs.span("serve.await", rids=rids):
                t_wait = self.clock()
                scores = np.asarray(res.scores)
                docs = np.asarray(res.doc_ids)
                t_wait = self.clock() - t_wait
            with obs.span("serve.reply", rids=rids):
                tc = self._tenant_counters(tenant)
                for i, p in enumerate(batch):
                    pair = (scores[i], docs[i])
                    self._results[p.req_id] = pair
                    self._inflight.discard(p.req_id)
                    tc["served"].inc()
                    if self.result_cache is not None and p.qkey is not None:
                        self.result_cache.put(
                            self._cache_key(p.qkey, p.fp), pair
                        )
            self._h_dispatch.observe(time.perf_counter() - t0)
            self._c["batches"].inc()
            self._c["padded_slots"].inc(b - len(batch))
            self._c["served"].inc(len(batch))
            self._c_step_host.inc(self.clock() - t_enter - t_wait)
        return len(batch)

    def drain(self) -> None:
        while len(self.scheduler):
            self.step(force=True)

    def summary(self) -> dict:
        """Merged serving statistics: dispatch counters, per-rung batch
        occupancy, cache hit rates, shed/admitted counts, epoch."""
        out = dict(self.stats)
        out["queue_depth"] = len(self.scheduler)
        out["promoted"] = self.scheduler.stats["promoted"]
        out["rungs"] = {
            str(r): dict(s) for r, s in self.scheduler.stats["rungs"].items()
        }
        out["rung_occupancy"] = {
            str(r): v for r, v in self.scheduler.occupancy().items()
        }
        out["index_epoch"] = self.index_epoch
        if self.result_cache is not None:
            out["result_cache"] = self.result_cache.stats()
            out["rung_cache"] = self._rung_cache.stats()
        if self.admission is not None:
            out["shed"] = self.admission.shed
            out["admitted"] = self.admission.admitted
        if len(self._tenants) > 1 or self._tenants[None].deleted:
            out["tenants"] = {
                ("default" if t is None else t): {
                    "submitted": int(
                        self._tenant_counters(t)["submitted"].value
                    ),
                    "served": int(self._tenant_counters(t)["served"].value),
                    "cache_hits": int(
                        self._tenant_counters(t)["cache_hits"].value
                    ),
                    "tombstones": len(st.deleted),
                    "n_docs": st.retriever.n_docs,
                }
                for t, st in self._tenants.items()
            }
        return out

    def health(self) -> dict:
        """Serving health report: ``{"status": "ok" | "degraded" |
        "overloaded", "reasons": [...], ...}``.

        *degraded* means the server is still answering but with reduced
        capability or redundancy — quarantined delta segments, the
        kernel executor demoted to the reference fallback, or failing
        background maintenance. *overloaded* means the admission gate is
        at its queue-depth limit and shedding. The status is also set on
        the ``serving_health_status`` gauge (0=ok, 1=degraded,
        2=overloaded) so scrapes see what ops would."""
        reasons = []
        depth = len(self.scheduler)
        overloaded = (
            self.admission is not None
            and depth >= self.admission.policy.max_queue_depth
        )
        if overloaded:
            reasons.append(
                f"queue depth {depth} at admission limit "
                f"{self.admission.policy.max_queue_depth}; shedding"
            )
        for t, st in self._tenants.items():
            lab = "" if t is None else f" (tenant {t!r})"
            if st.quarantined:
                reasons.append(
                    f"quarantined delta segment(s){lab}: "
                    + ", ".join(st.quarantined)
                )
            if st.plan.fallback_active:
                reasons.append(
                    f"kernel executor demoted to reference fallback{lab}"
                )
        if self._maintain_failures:
            reasons.append(
                f"maintenance failing (x{self._maintain_failures}): "
                f"{self._maintain_error}"
            )
        status = "overloaded" if overloaded else (
            "degraded" if reasons else "ok"
        )
        self._g_health.set({"ok": 0, "degraded": 1, "overloaded": 2}[status])
        return {
            "status": status,
            "reasons": reasons,
            "queue_depth": depth,
            "index_epoch": self.index_epoch,
            "quarantined_segments": list(self._quarantined),
            "executor_fallback": bool(self.plan.fallback_active),
            "maintain_failures": self._maintain_failures,
            "tenants": [
                "default" if t is None else t for t in self.tenants
            ],
        }
