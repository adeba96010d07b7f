"""Observability substrate (``repro.obs``): deterministic metrics +
tracing, exposition goldens, and the load-bearing claims that tracing
changes nothing about the retrieve program (the same compiled callable,
no fence, no compilation, bit-identical results) while every op of that
program carries its stage's ``warp.*`` named scope for the device trace,
and that server spans also reach the profiler's trace."""

import dataclasses
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (
    IndexBuildConfig,
    Retriever,
    WarpSearchConfig,
    build_index,
    engine,
)
from repro.data import make_corpus, make_queries
from repro.obs import (
    Histogram,
    MetricsRegistry,
    Span,
    Stopwatch,
    Tracer,
    percentiles,
    span_tree,
    time_fn,
)
from repro.serving import BatchPolicy, BucketScheduler, RetrievalServer

RAGGED = WarpSearchConfig(nprobe=8, k=5, t_prime=400, layout="ragged")


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends at the zero-overhead default."""
    obs.disable_all()
    yield
    obs.disable_all()


@pytest.fixture(scope="module")
def setup():
    corpus = make_corpus(n_docs=250, mean_doc_len=12, seed=0)
    idx = build_index(
        corpus.emb, corpus.token_doc_ids, corpus.n_docs,
        IndexBuildConfig(n_centroids=64, nbits=4, kmeans_iters=3),
    )
    q, qmask, rel = make_queries(
        corpus, n_queries=6, tokens_per_query=(2, 24), seed=1
    )
    return corpus, idx, q, qmask, rel


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", kind="a")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    # Same (name, labels) -> same object; different labels -> new series.
    assert reg.counter("reqs_total", kind="a") is c
    assert reg.counter("reqs_total", kind="b") is not c
    g = reg.gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4


def test_metric_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_histogram_quantiles_deterministic():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.quantile(0.5) == 0.0  # empty
    for v in (0.5, 1.5, 1.5, 3.0, 3.0, 3.0, 7.0, 9.0):
        h.observe(v)
    assert h.count == 8
    assert h.min == 0.5 and h.max == 9.0
    # Same stream -> same quantiles, clamped to [min, max]; the +Inf
    # bucket interpolates toward the observed max, not infinity.
    q50_a = h.quantile(0.5)
    h2 = Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 3.0, 3.0, 7.0, 9.0):
        h2.observe(v)
    assert h2.quantile(0.5) == q50_a
    assert h.min <= h.quantile(0.01)
    assert h.quantile(0.999) <= h.max
    assert h.percentile(50.0) == q50_a
    with pytest.raises(ValueError):  # non-ascending edges
        Histogram("bad", buckets=(2.0, 1.0))


def test_percentiles_is_np_percentile():
    rng = np.random.default_rng(3)
    xs = rng.exponential(1.0, 101)
    p50, p95, p99 = percentiles(xs)
    np.testing.assert_allclose(
        [p50, p95, p99], np.percentile(xs, [50, 95, 99])
    )
    assert percentiles([]) == (0.0, 0.0, 0.0)


def test_time_fn_injectable_clock_and_sync():
    clock = _FakeClock()
    synced = []

    def fn():
        clock.tick(0.25)
        return "out"

    t = time_fn(fn, warmup=1, iters=3, clock=clock, sync=synced.append)
    assert t == pytest.approx(0.25)
    assert synced == ["out"] * 4  # warmup + iters all synced


def test_stopwatch():
    clock = _FakeClock()
    h = Histogram("d", buckets=(1.0, 10.0))
    with Stopwatch(clock=clock, hist=h) as sw:
        clock.tick(2.0)
    assert sw.elapsed == 2.0
    assert h.count == 1 and h.sum == 2.0


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("req_total", "Requests", kind="s").inc(3)
    reg.gauge("depth", "Queue depth").set(2)
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert reg.to_prometheus() == (
        "# HELP depth Queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_seconds Latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n"
        "# HELP req_total Requests\n"
        "# TYPE req_total counter\n"
        'req_total{kind="s"} 3\n'
    )


def test_snapshot_json_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c_total", "c", kind="x").inc(2)
    reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["series"][0] == {
        "labels": {"kind": "x"}, "value": 2.0,
    }
    hs = snap["h_seconds"]["series"][0]
    assert hs["count"] == 1 and hs["counts"] == [1, 0]


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_tree_deterministic_with_fake_clock():
    clock = _FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("root", kind="r"):
        clock.tick()
        with tr.span("a"):
            clock.tick()
        with tr.span("b") as sp:
            sp.set(extra=1)
            clock.tick(2.0)
    tree = span_tree(tr.events())
    assert len(tree) == 1
    root = tree[0]
    assert root["span"].name == "root"
    assert root["span"].ts == 0.0 and root["span"].dur == 4.0
    assert [c["span"].name for c in root["children"]] == ["a", "b"]
    b = root["children"][1]["span"]
    assert (b.ts, b.dur) == (2.0, 2.0)
    assert b.args == {"extra": 1}


def test_tracer_ring_capacity_and_dropped():
    clock = _FakeClock()
    tr = Tracer(clock=clock, capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 4
    assert [s.name for s in evs] == ["e6", "e7", "e8", "e9"]  # oldest drop
    assert tr.dropped == 6
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_chrome_export_roundtrip(tmp_path):
    clock = _FakeClock()
    tr = Tracer(clock=clock, pid=1)
    with tr.span("outer"):
        clock.tick(0.001)
        with tr.span("inner"):
            clock.tick(0.002)
    tr.add_event("wait", 0.0, 0.0005, tid=42, rung=8)
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"outer", "inner", "wait"}
    # ts/dur are microseconds; nesting must survive the unit conversion.
    assert evs["outer"]["ph"] == "X"
    assert evs["outer"]["ts"] == 0.0 and evs["outer"]["dur"] == 3000.0
    assert evs["inner"]["ts"] == 1000.0 and evs["inner"]["dur"] == 2000.0
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"])
    assert evs["wait"]["tid"] == 42 and evs["wait"]["args"] == {"rung": 8}
    assert all(e["pid"] == 1 for e in evs.values())


def test_null_tracer_is_free_shape():
    # Disabled call sites share the same singletons — no allocation.
    s1 = obs.span("x")
    s2 = obs.span("y", a=1)
    assert s1 is s2 is obs.NULL_SPAN
    with s1 as sp:
        assert sp.set(a=2) is sp
    assert obs.tracer() is obs.NULL_TRACER
    assert obs.tracer().events() == []


# ---------------------------------------------------------------------------
# instrumented retrieve path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    WarpSearchConfig(nprobe=8, k=5, t_prime=400),  # dense
    RAGGED,                                        # adaptive ragged
], ids=["dense", "ragged"])
def test_traced_retrieve_bit_identical(setup, cfg):
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(cfg)
    base = [plan.retrieve(q[i], qmask[i]) for i in range(4)]
    base_b = plan.retrieve_batch(q[:4], qmask[:4])

    obs.set_tracer(Tracer())
    traced = [plan.retrieve(q[i], qmask[i]) for i in range(4)]
    traced_b = plan.retrieve_batch(q[:4], qmask[:4])
    for a, b in zip(base, traced):
        np.testing.assert_array_equal(
            np.asarray(a.doc_ids), np.asarray(b.doc_ids)
        )
        np.testing.assert_array_equal(
            np.asarray(a.scores), np.asarray(b.scores)
        )
    np.testing.assert_array_equal(
        np.asarray(base_b.doc_ids), np.asarray(traced_b.doc_ids)
    )


def test_traced_spans_cover_stages(setup):
    """A traced retrieve is one ``retrieve`` span around the compiled
    callable; the stages are scopes inside the program, not spans
    (``test_compiled_ops_carry_one_stage_scope``)."""
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(RAGGED)
    plan.retrieve(q[0], qmask[0])  # compile untraced first
    tr = obs.set_tracer(Tracer())
    plan.retrieve(q[0], qmask[0])
    tree = span_tree(tr.events())
    assert [n["span"].name for n in tree] == ["retrieve"]
    assert tree[0]["children"] == []
    root = tree[0]["span"]
    assert root.args == {"kind": "single", "layout": "ragged", "n_shards": 1}


def test_traced_batch_at_parity(setup):
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(RAGGED)
    rung = plan.config.worklist_buckets[-1]
    base = plan.retrieve_batch_at(q[:3], qmask[:3], bucket=rung)
    tr = obs.set_tracer(Tracer())
    traced = plan.retrieve_batch_at(q[:3], qmask[:3], bucket=rung)
    np.testing.assert_array_equal(
        np.asarray(base.doc_ids), np.asarray(traced.doc_ids)
    )
    np.testing.assert_array_equal(
        np.asarray(base.scores), np.asarray(traced.scores)
    )
    # Forced rung: one retrieve span of that kind, nothing inside it.
    assert [(s.name, s.args["kind"]) for s in tr.events()] == [
        ("retrieve", "batch_at")
    ]


def test_metrics_only_counts_retrieves(setup):
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(RAGGED)
    reg = obs.enable_metrics(MetricsRegistry())
    for i in range(3):
        plan.retrieve(q[i], qmask[i])
    plan.retrieve_batch(q[:2], qmask[:2])
    assert reg.counter("warp_retrieves_total", kind="single").value == 3
    assert reg.counter("warp_retrieves_total", kind="batch").value == 1
    h = reg.histogram("warp_retrieve_seconds", kind="single")
    assert h.count == 3 and h.sum > 0
    # With a tracer too, a retrieve still counts once, and nothing
    # records per-stage host times.
    obs.set_tracer(Tracer())
    plan.retrieve(q[0], qmask[0])
    assert reg.counter("warp_retrieves_total", kind="single").value == 4
    assert all(m.name.startswith("warp_retrieve") for m in reg.metrics())


_STAGE_OPS = ("sort", "gather", "scatter", "dot", "custom-call")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")


def _unscoped_stage_ops(hlo: str) -> tuple[list, set]:
    """(instructions of the stage opcodes without exactly one ``warp.*``
    scope, every scope seen) in an optimized HLO module's text. Under
    ``vmap`` a scope reads ``vmap(warp.reduce)/...``."""
    bad, seen = [], set()
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(1) not in _STAGE_OPS:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = set(re.findall(
            r"warp\.(?:select|gather_score|reduce)(?=[/)])",
            name.group(1) if name else "",
        ))
        seen |= scopes
        if len(scopes) != 1:
            bad.append(line.strip())
    return bad, seen


@pytest.mark.parametrize("cfg", [
    WarpSearchConfig(nprobe=8, k=5, t_prime=400),  # dense
    RAGGED,                                        # adaptive ragged
], ids=["dense", "ragged"])
def test_compiled_ops_carry_one_stage_scope(setup, cfg):
    """Every sort, gather, scatter, dot and custom call of the optimized
    retrieve program names exactly one stage scope — the batch program
    the serving path runs, and the adaptive plan's select_probes ->
    finish_from_probes pair."""
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(cfg)
    c, ix = plan.config, plan._index
    qb, mb = jnp.asarray(q[:4], jnp.float32), jnp.asarray(qmask[:4], bool)
    texts = [engine._search_many.lower(ix, qb, mb, c).compile().as_text()]
    if c.worklist_buckets:
        sel = engine.select_probes(ix, qb, mb, c, True)
        texts.append(
            engine.select_probes.lower(ix, qb, mb, c, True).compile().as_text()
        )
        fcfg = dataclasses.replace(
            c, worklist_tiles=c.worklist_buckets[-1], worklist_buckets=None
        )
        texts.append(
            engine.finish_from_probes.lower(ix, qb, mb, sel, fcfg, True)
            .compile().as_text()
        )
    seen = set()
    for text in texts:
        bad, got = _unscoped_stage_ops(text)
        assert bad == [], bad[:5]
        seen |= got
    assert seen == set(engine.STAGE_SCOPES)


@pytest.mark.parametrize("cfg", [
    WarpSearchConfig(nprobe=8, k=5, t_prime=400),  # dense
    RAGGED,                                        # adaptive ragged
], ids=["dense", "ragged"])
def test_tracer_after_warmup_compiles_nothing(setup, cfg):
    """Installing a tracer after warm-up runs the same compiled programs:
    no compilation event, and bit-identical results."""
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(cfg)
    base = plan.retrieve_batch(q[:4], qmask[:4])
    compiles = []

    def listen(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        obs.set_tracer(Tracer())
        traced = plan.retrieve_batch(q[:4], qmask[:4])
        jax.block_until_ready(traced)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    np.testing.assert_array_equal(
        np.asarray(base.doc_ids), np.asarray(traced.doc_ids)
    )
    np.testing.assert_array_equal(
        np.asarray(base.scores), np.asarray(traced.scores)
    )


def test_spans_reach_the_profiler_trace(tmp_path):
    """While a profiler session records, ``obs.span`` also writes a
    ``TraceAnnotation`` of the same name, with or without a tracer; a
    list argument arrives as one space-separated stat."""
    from jax.profiler import ProfileData

    assert obs.span("serve.step") is obs.NULL_SPAN  # no session, no tracer
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serve.step", rids=[3, 4]):
            with obs.span("serve.await", rids=[3, 4]) as sp:
                sp.set(rung=8)
        tr = obs.set_tracer(Tracer())
        with obs.span("retrieve", kind="batch"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in tr.events()] == ["retrieve"]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    got = {
        ev.name: dict(ev.stats)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name in ("serve.step", "serve.await", "retrieve")
    }
    assert got == {
        "serve.step": {"rids": "3 4"},
        "serve.await": {"rids": "3 4", "rung": 8},
        "retrieve": {"kind": "batch"},
    }


def test_disabled_dispatch_overhead_smoke(setup):
    """Loose CPU smoke bound; the real margin is measured and committed
    by benchmarks/bench_obs.py (BENCH_obs.json, < 2%)."""
    _, idx, q, qmask, _ = setup
    plan = Retriever.from_index(idx).plan(RAGGED)
    q0, m0 = jnp.asarray(q[0], jnp.float32), jnp.asarray(qmask[0], bool)
    import jax as _jax
    base = time_fn(
        plan._single, plan._index, q0, m0,
        warmup=2, iters=9, sync=_jax.block_until_ready,
    )
    disp = time_fn(
        plan.retrieve, q0, m0,
        warmup=2, iters=9, sync=_jax.block_until_ready,
    )
    assert disp <= 2.0 * base + 1e-3, (base, disp)


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


def test_serving_end_to_end_trace(setup):
    """One request's lifecycle shows up as ``serve.*`` spans: submit
    (rung pre-pass) -> queue_wait -> step (assemble, dispatch, await,
    reply), each carrying request ids, with server and tracer sharing one
    injected clock."""
    _, idx, q, qmask, _ = setup
    clock = _FakeClock()
    server = RetrievalServer(
        Retriever.from_index(idx), RAGGED,
        BatchPolicy(max_batch=2, max_wait_s=10.0), clock,
    )
    tr = obs.set_tracer(Tracer(clock=clock))
    r0 = server.submit(q[0], qmask[0])
    clock.tick(0.5)
    r1 = server.submit(q[1], qmask[1])
    clock.tick(0.25)
    assert server.step(force=True) == 2
    events = tr.events()
    names = [s.name for s in events]
    for name in ("serve.submit", "serve.rung_prepass", "serve.queue_wait",
                 "serve.step", "serve.assemble", "serve.dispatch",
                 "retrieve", "serve.await", "serve.reply"):
        assert name in names, (name, names)
    assert not [n for n in names if n not in ("retrieve",)
                and not n.startswith("serve.")]
    waits = {s.tid: s for s in events if s.name == "serve.queue_wait"}
    assert set(waits) == {r0, r1}
    # Shared clock: the waits are exact and end at the dispatch instant.
    assert waits[r0].dur == pytest.approx(0.75)
    assert waits[r1].dur == pytest.approx(0.25)
    assert waits[r0].end == pytest.approx(0.75)
    subs = [s.args["rid"] for s in events if s.name == "serve.submit"]
    pre = [s.args["rid"] for s in events if s.name == "serve.rung_prepass"]
    assert subs == pre == [r0, r1]
    step = next(s for s in events if s.name == "serve.step")
    assert step.args["batch_size"] == 2
    assert sorted(step.args["rids"]) == [r0, r1]
    # The four phases of the step inside it, in order (spans record as
    # they close), each with the ids.
    inner = [s for s in events if s.name != "serve.queue_wait"
             and s.ts >= step.ts and s.end <= step.end and s is not step]
    assert [s.name for s in inner if s.name.startswith("serve.")][-4:] == [
        "serve.assemble", "serve.dispatch", "serve.await", "serve.reply"
    ]
    assert all(sorted(s.args["rids"]) == [r0, r1]
               for s in inner if s.name.startswith("serve."))
    assert events[-1] is step
    assert server.poll(r0) is not None and server.poll(r1) is not None


def test_server_stats_backcompat_and_registry(setup):
    _, idx, q, qmask, _ = setup
    server = RetrievalServer(
        Retriever.from_index(idx), RAGGED,
        BatchPolicy(max_batch=4, max_wait_s=10.0), _FakeClock(),
    )
    for i in range(3):
        server.submit(q[i], qmask[i])
    server.drain()
    st = server.stats
    assert st["served"] == 3 and st["batches"] >= 1
    assert set(st) == {"batches", "padded_slots", "served", "reloads",
                       "cache_hits", "compactions", "deadline_shed",
                       "maintain_retries", "queue_wait_us", "step_host_us"}
    assert all(type(v) is int for v in st.values())
    # The same numbers are Prometheus-visible through the registry.
    text = server.metrics.to_prometheus()
    assert "serving_requests_served_total 3" in text
    assert "serving_queue_wait_seconds_count" in text
    assert "serving_step_host_seconds_total" in text
    snap = server.metrics.snapshot()
    assert snap["serving_batches_total"]["series"][0]["value"] == st["batches"]
    # Private registry per server: a second server starts at zero.
    other = RetrievalServer(
        Retriever.from_index(idx), RAGGED,
        BatchPolicy(max_batch=4, max_wait_s=10.0), _FakeClock(),
    )
    assert other.stats["served"] == 0


def test_stats_queue_wait_and_step_host_exact(setup):
    """``queue_wait_us`` sums submit-to-dispatch waits and
    ``step_host_us`` the host time of ``step`` outside the device wait,
    both on the server clock: exact under a fake clock that the plan
    call (host) and the result copy (device wait) advance."""
    _, idx, q, qmask, _ = setup
    clock = _FakeClock()
    server = RetrievalServer(
        Retriever.from_index(idx), WarpSearchConfig(nprobe=8, k=5),
        BatchPolicy(max_batch=4, max_wait_s=10.0), clock, cache_size=0,
    )
    plan = server.plan

    class _Waits:
        """A device result whose copy to the host takes 2 s."""

        def __init__(self, a):
            self.a = a

        def __array__(self, dtype=None, copy=None):
            clock.tick(1.0)
            return np.asarray(self.a, dtype)

    class _Plan:
        config = plan.config

        def retrieve_batch(self, qd, md):
            clock.tick(0.25)  # host time of the enqueue
            res = plan.retrieve_batch(qd, md)
            return res._replace(
                scores=_Waits(res.scores), doc_ids=_Waits(res.doc_ids)
            )

    server.plan = _Plan()
    r = [server.submit(q[0], qmask[0])]
    clock.tick(0.5)
    r.append(server.submit(q[1], qmask[1]))
    clock.tick(0.125)
    assert server.step(force=True) == 2
    st = server.stats
    assert st["queue_wait_us"] == 625_000 + 125_000
    assert st["step_host_us"] == 250_000
    # An empty step adds its (zero) host time and no wait.
    assert server.step(force=True) == 0
    assert server.stats["step_host_us"] == 250_000
    assert all(server.poll(i) is not None for i in r)


def test_scheduler_stats_property_reconstruction():
    class _Item:
        def __init__(self, arrival):
            self.arrival = arrival

    clock = _FakeClock()
    sched = BucketScheduler(
        BatchPolicy(max_batch=2, max_wait_s=1.0, promote_after_s=100.0),
        clock, rungs=(4, 8),
    )
    sched.push(_Item(0.0), 4)
    sched.push(_Item(0.0), 4)
    rung, items = sched.next_batch()
    assert rung == 4 and len(items) == 2
    st = sched.stats
    assert st["promoted"] == 0
    assert st["rungs"] == {
        4: {"batches": 1, "requests": 2, "slots": 2, "backfilled": 0}
    }
    assert sched.occupancy() == {4: 1.0}
    # Queue-wait histogram recorded per dispatched item.
    h = sched.metrics.histogram("serving_queue_wait_seconds", rung="4")
    assert h.count == 2


def test_store_delta_gauges(tmp_path, setup):
    corpus, idx, _, _, _ = setup
    from repro.store import delta_stats, save_index

    path = str(tmp_path / "store")
    reg = obs.enable_metrics(MetricsRegistry())
    save_index(idx, path)
    stats = delta_stats(path)
    assert stats["n_delta_segments"] == 0
    assert reg.gauge("store_delta_segments").value == 0
    assert reg.histogram("store_save_seconds").count == 1
    assert reg.gauge("store_delta_token_frac").value == 0.0


# ---------------------------------------------------------------------------
# kernel probe carve-outs through the ops wrappers
# ---------------------------------------------------------------------------


def test_ops_probe_rejects_reference_fallback(setup):
    """Kernel probe carve-outs (probe="dma"/"compute") only make sense on
    the Pallas path — asking the jnp reference for them must fail loud,
    not silently return full-kernel numbers."""
    from repro.kernels import ops

    _, idx, q, _, _ = setup
    probe_cids = jnp.zeros((1, 2), jnp.int32)
    probe_scores = jnp.zeros((1, 2), jnp.float32)
    v = jnp.zeros((1, idx.dim, 2 ** idx.nbits), jnp.float32)
    with pytest.raises(ValueError, match="probe"):
        ops.fused_gather_selective_sum(
            idx.packed_codes, idx.cluster_offsets, idx.cluster_sizes,
            probe_cids, probe_scores, v,
            nbits=idx.nbits, dim=idx.dim, cap=idx.cap,
            n_tokens=idx.n_tokens, use_kernel=False, probe="dma",
        )


# ---------------------------------------------------------------------------
# benchmark suite smoke
# ---------------------------------------------------------------------------


def test_bench_obs_micro_and_snapshot(tmp_path):
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import bench_obs, run as bench_run

    bench_obs.run(micro=True)
    snap_path = str(tmp_path / "BENCH_obs.json")
    bench_run.write_obs_snapshot(snap_path)
    snap = json.load(open(snap_path))
    assert snap["bench_schema"] >= 2
    for arm in ("no_obs", "disabled", "metrics", "tracing"):
        assert arm in snap["arms"]
        assert snap["arms"][arm]["us_per_call"] > 0
    assert all(r["name"].startswith("obs/") for r in snap["metrics"])
    # The suite must leave the process at the zero-overhead default.
    assert obs.STATE.tracer is None and obs.STATE.metrics is None
