#!/usr/bin/env python3
"""Chip smoke: the WARP retrieval main path, build -> serve, on a TPU.

    python chip_smoke.py                 # one chip, LoTTE-Lifestyle size
    python chip_smoke.py --chips 4       # document-sharded over four chips

One chip: builds a LoTTE-Lifestyle-size index on the chip from ``--seed``
(23.71M tokens, 119,461 docs, 2^17 centroids, dim 128, nbits 4 — paper
Table 4, ``configs/warp_family.py``) through the chunked builder, stands
up a ``RetrievalServer`` per kernel variant with
``WarpSearchConfig(nprobe=32, k=100, executor="kernel")``
(``configs/warp_xtr.py``), and answers the queries in each:

  materialize-dense   gather="materialize" (the default), dense layout
  fused-dense         gather="fused", dense layout
  fused-ragged        gather="fused", layout="ragged" (adaptive worklist)

Every answer is compared with the same plan under
``executor="reference"`` on the same index: scores within 1e-5 relative,
top-k doc ids equal except where scores tie within that tolerance.

``--chips 4`` runs only the document-sharded path: the same corpus in
four shards, shard s placed on device s, served through the sharded plan
with the kernel executor and compared with the reference executor on the
same sharded index.

The run fails (non-zero exit, no result line) when JAX finds no TPU, when
a plan was demoted to the reference executor, when a variant's compiled
program holds no Pallas kernel (``tpu_custom_call``), on any parity
mismatch, and on any exception. Otherwise the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.configs import warp_xtr
    from repro.configs.warp_family import WARP_SHAPES
    from repro.core import IndexBuildConfig, Retriever, WarpSearchConfig
    from repro.core import distributed as dist
    from repro.core import engine
    from repro.data import make_queries, make_streamed_corpus
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.serving import BatchPolicy, RetrievalServer
    from repro.store.builder import build_index_chunked
except ImportError as e:  # run outside a checkout of the repository
    sys.exit(f"chip_smoke: cannot import the repro package next to {__file__}: {e}")

RTOL = 1e-5
SHAPE = WARP_SHAPES["search_lifestyle"]
ARCH = warp_xtr.CONFIG
VARIANTS = {
    "materialize-dense": dict(gather="materialize", layout="dense"),
    "fused-dense": dict(gather="fused", layout="dense"),
    "fused-ragged": dict(gather="fused", layout="ragged"),
}


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def log(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------


def require_tpu(count: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {len(devices)} {devices[0].platform} "
            "device(s); this smoke runs only on the chip"
        )
    if len(devices) < count:
        raise SmokeFailure(f"need {count} TPU chips, JAX found {len(devices)}")
    return devices[:count]


def check_no_fallback(plan, registry) -> None:
    """Fail when the kernel plan was demoted to the reference executor,
    however well the demoted server still answers."""
    if plan.warmup():
        raise SmokeFailure("plan.warmup() reports an executor fallback")
    if plan.fallback_active:
        raise SmokeFailure(
            f"plan fell back to the reference executor: "
            f"{plan._fallback.get('error')}"
        )
    n = registry.counter("warp_executor_fallbacks_total").value
    if n > 0:
        raise SmokeFailure(f"warp_executor_fallbacks_total = {n:g}")


def batch_program_text(plan, index, q, qmask, *, mesh=None) -> str:
    """Optimized HLO of the program a batch of one query runs through
    ``plan`` (the server dispatches such batches), at the rung the
    adaptive dispatcher picks for it."""
    cfg = plan.config
    bucket = plan.adaptive_bucket(q[0], qmask[0])
    if bucket is not None:
        cfg = dataclasses.replace(cfg, worklist_tiles=bucket, worklist_buckets=None)
    if mesh is not None:
        fn = dist.make_sharded_search_fn(index, cfg, mesh, query_batch=True)
        lowered = fn.lower(index, q, qmask)
    elif bucket is None:
        lowered = engine._search_many.lower(index, q, qmask, cfg)
    else:
        sel = engine.select_probes(index, q, qmask, cfg, True)
        lowered = engine.finish_from_probes.lower(index, q, qmask, sel, cfg, True)
    return lowered.compile().as_text()


def check_kernel_in_program(name: str, text: str) -> None:
    if "tpu_custom_call" not in text:
        raise SmokeFailure(f"{name}: the compiled retrieve program has no Pallas kernel")


def _score_close(a: float, b: float) -> bool:
    return a == b or abs(a - b) <= RTOL * max(abs(a), abs(b))


def check_parity(name: str, i: int, got, want) -> int:
    """Served (scores, doc_ids) against the reference's. Scores must agree
    position by position within RTOL. A doc-id mismatch at a position is a
    tie only if each result's doc there appears in the other result with
    an agreeing score, or — when it fell out of the other's top-k — scores
    within RTOL of the other's k-th score. Returns the tie swaps."""
    s, d = np.asarray(got[0], np.float64), np.asarray(got[1])
    r, e = np.asarray(want[0], np.float64), np.asarray(want[1])
    for p in range(len(r)):
        if not _score_close(s[p], r[p]):
            raise SmokeFailure(
                f"{name} query {i}: score at rank {p} is {s[p]!r}, the "
                f"reference has {r[p]!r}"
            )
    swaps = 0
    for p in np.flatnonzero(d != e):
        for doc, score, other_ids, other_scores in (
            (d[p], s[p], e, r), (e[p], r[p], d, s),
        ):
            hit = np.flatnonzero(other_ids == doc)
            ok = (
                _score_close(score, other_scores[hit[0]])
                if hit.size
                else _score_close(score, other_scores[-1])
            )
            if not ok:
                raise SmokeFailure(
                    f"{name} query {i}: doc {doc} at rank {p} does not tie "
                    "in the other executor's top-k"
                )
        swaps += 1
    return swaps


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Scale:
    n_tokens: int
    n_docs: int
    n_centroids: int


def scale_for(n_tokens: int | None) -> Scale:
    """Lifestyle geometry, or a token-count cut that keeps tokens per doc
    and per centroid (dim, nbits, nprobe, k and query_maxlen never change)."""
    if not n_tokens or n_tokens >= SHAPE.n_tokens:
        return Scale(SHAPE.n_tokens, SHAPE.n_docs, SHAPE.n_centroids)
    frac = n_tokens / SHAPE.n_tokens
    c = 1 << max(4, round(np.log2(SHAPE.n_centroids * frac)))
    return Scale(n_tokens, max(1, round(SHAPE.n_docs * frac)), c)


def build(corpus, n_docs: int, n_centroids: int, seed: int, *, tok_lo=0, tok_hi=None, doc_lo=0):
    """Chunked build of tokens ``[tok_lo, tok_hi)`` (doc ids local to
    ``doc_lo``); returns the index with host-side arrays."""
    tok_hi = corpus.n_tokens if tok_hi is None else tok_hi

    def chunks():
        for emb, tdi in corpus.chunks(tok_lo, tok_hi):
            yield emb, tdi - doc_lo

    cfg = IndexBuildConfig(n_centroids=n_centroids, nbits=ARCH.nbits, seed=seed)
    return build_index_chunked(
        chunks, n_docs, cfg, n_tokens=tok_hi - tok_lo, dim=corpus.dim
    )


def device_bytes(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


@dataclasses.dataclass
class VariantRun:
    name: str
    server: RetrievalServer
    answers: list
    latencies_s: list
    compile_s: float


def serve_variant(name, retriever, q, qmask, warm_q, warm_mask, **strategy) -> VariantRun:
    """Stand up a kernel-executor server for one variant and answer every
    query one request at a time (batch of one, result cache off)."""
    cfg = WarpSearchConfig(
        nprobe=ARCH.nprobe, k=ARCH.k, executor="kernel", **strategy,
    )
    t0 = time.perf_counter()
    server = RetrievalServer(
        retriever, cfg, BatchPolicy(max_batch=1, max_wait_s=0.0), cache_size=0
    )
    plan = server.plan
    # Compile every program the timed requests will run: the batch of one
    # at each worklist rung the queries pick (one program on dense plans).
    rungs = {plan.adaptive_bucket(q[i], qmask[i]) for i in range(len(q))}
    for rung in rungs:
        if rung is None:
            out = plan.retrieve_batch(warm_q[None], warm_mask[None])
        else:
            out = plan.retrieve_batch_at(warm_q[None], warm_mask[None], bucket=rung)
        jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    answers, lat = [], []
    for i in range(len(q)):
        t = time.perf_counter()
        rid = server.submit(q[i], qmask[i])
        server.drain()
        answers.append(server.poll(rid))
        lat.append(time.perf_counter() - t)
    return VariantRun(name, server, answers, lat, compile_s)


def reference_answers(retriever, plan, q, qmask) -> list:
    ref = retriever.plan(dataclasses.replace(plan.config, executor="reference"))
    out = []
    for i in range(len(q)):
        res = ref.retrieve_batch(q[i][None], qmask[i][None])
        out.append((np.asarray(res.scores)[0], np.asarray(res.doc_ids)[0]))
    return out


def run_variants(retriever, q, qmask, warm_q, warm_mask, registry, *, names, mesh=None) -> None:
    for name in names:
        run = serve_variant(name, retriever, q, qmask, warm_q, warm_mask, **VARIANTS[name])
        plan = run.server.plan
        check_no_fallback(plan, registry)
        text = batch_program_text(
            plan, retriever.index, jnp.asarray(q[:1]), jnp.asarray(qmask[:1]), mesh=mesh
        )
        check_kernel_in_program(name, text)
        want = reference_answers(retriever, plan, q, qmask)
        swaps = sum(check_parity(name, i, run.answers[i], want[i]) for i in range(len(q)))
        check_no_fallback(plan, registry)
        lat_ms = [t * 1e3 for t in run.latencies_s]
        d = plan.describe()
        log(
            f"variant {name}: tile_c={d['tile_c']} ({d['tile_source']}) "
            f"buffering={d['buffering']} "
            f"worklist_buckets={d['worklist_buckets']} compile_s={run.compile_s:.1f} "
            f"requests={len(lat_ms)} latency_ms median={statistics.median(lat_ms):.3f} "
            f"max={max(lat_ms):.3f} kernel_in_program=yes"
        )
        log(
            f"parity {name}: {len(q)}/{len(q)} queries match the reference "
            f"executor within rtol={RTOL} (tie swaps: {swaps})"
        )


def make_workload(args, scale: Scale):
    corpus = make_streamed_corpus(
        scale.n_tokens, scale.n_docs, ARCH.dim, seed=args.seed
    )
    q, qmask, _ = make_queries(
        corpus, args.queries + 1, query_maxlen=ARCH.query_maxlen,
        tokens_per_query=ARCH.query_maxlen, seed=args.seed + 1,
    )
    # The last query only warms the compiled programs.
    return corpus, q[:-1], qmask[:-1], q[-1], qmask[-1]


def run_one_chip(args, scale: Scale, registry) -> None:
    corpus, q, qmask, warm_q, warm_mask = make_workload(args, scale)
    t0 = time.perf_counter()
    index = jax.device_put(build(corpus, scale.n_docs, scale.n_centroids, args.seed))
    jax.block_until_ready(index)
    build_s = time.perf_counter() - t0
    log(
        f"index: n_tokens={index.n_tokens} n_docs={index.n_docs} "
        f"n_centroids={index.n_centroids} cap={index.cap} dim={index.dim} "
        f"nbits={index.nbits} codes_bytes={index.packed_codes.nbytes} "
        f"doc_id_bytes={index.token_doc_ids.nbytes} "
        f"centroid_bytes={index.centroids.nbytes} build_s={build_s:.1f}"
    )
    retriever = Retriever.from_index(index)
    run_variants(retriever, q, qmask, warm_q, warm_mask, registry, names=list(VARIANTS))
    log(f"memory: {device_bytes(jax.devices()[0])}")


def run_four_chips(args, scale: Scale, registry, devices) -> None:
    """Document-sharded path: contiguous token-balanced doc ranges, one
    chunked build per shard, shard s placed on device s."""
    n = len(devices)
    corpus, q, qmask, warm_q, warm_mask = make_workload(args, scale)
    doc_off = np.concatenate([[0], np.cumsum(corpus.doc_lens, dtype=np.int64)])
    bounds = np.searchsorted(doc_off, np.linspace(0, corpus.n_tokens, n + 1)[1:-1])
    doc_bounds = [0, *map(int, bounds), corpus.n_docs]
    t0 = time.perf_counter()
    shards = []
    for s in range(n):
        lo, hi = doc_bounds[s], doc_bounds[s + 1]
        shards.append(build(
            corpus, hi - lo, scale.n_centroids // n, args.seed + s,
            tok_lo=int(doc_off[lo]), tok_hi=int(doc_off[hi]), doc_lo=lo,
        ))
    mesh = make_mesh((n,), ("data",))
    sidx = dist.stack_shards(
        shards, doc_bounds[:-1], corpus.n_docs, corpus.n_tokens, mesh=mesh
    )
    del shards
    jax.block_until_ready(sidx)
    homes = {sh.device for sh in sidx.packed_codes.addressable_shards}
    if len(homes) != n or sidx.packed_codes.addressable_shards[0].data.shape[0] != 1:
        raise SmokeFailure(
            f"sharded codes are not one shard per device: {sidx.packed_codes.sharding}"
        )
    log(
        f"sharded index: shards={n} n_tokens={corpus.n_tokens} "
        f"n_docs={corpus.n_docs} n_centroids={scale.n_centroids} "
        f"cap={sidx.cap} tokens_per_shard={sidx.n_tokens_padded} "
        f"build_s={time.perf_counter() - t0:.1f}"
    )
    for dev in devices:
        log(f"placement: {dev} {device_bytes(dev)}")
    retriever = Retriever.from_index(sidx, mesh=mesh)
    run_variants(
        retriever, q, qmask, warm_q, warm_mask, registry, names=list(VARIANTS), mesh=mesh
    )
    for dev in devices:
        log(f"memory: {dev} {device_bytes(dev)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=None,
                    help="cut the corpus to this many tokens (docs and "
                         "centroids scale with it); default: Lifestyle")
    args = ap.parse_args(argv)

    cache = setup_compile_cache()
    try:
        devices = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    scale = scale_for(args.tokens)
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache: {cache}")
    if scale.n_tokens != SHAPE.n_tokens:
        log(
            f"scale cut: {scale.n_tokens} of {SHAPE.n_tokens} tokens, "
            f"{scale.n_docs} docs, {scale.n_centroids} centroids"
        )
    registry = obs.enable_metrics(obs.MetricsRegistry())
    if args.chips == 1:
        run_one_chip(args, scale, registry)
    else:
        run_four_chips(args, scale, registry, devices)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
