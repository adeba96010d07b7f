"""Observability overhead benchmark: what does instrumentation cost?

The obs substrate (``repro.obs``) promises a near-zero-cost disabled
default on the retrieve hot path — two attribute checks and a check for
a recording profiler in ``SearchPlan._dispatch`` — and a tracing state
that runs the same compiled program under one ``retrieve`` span, with
no fence. This suite pins both claims to numbers, per arm:

  no_obs     the raw compiled callable (``plan._single``) on
             pre-converted device arrays — the zero-instrumentation
             floor the dispatch path is compared against
  disabled   ``plan.retrieve`` with obs fully off (the default every
             test and benchmark runs under) — the acceptance bound is
             < 2% over no_obs
  metrics    ``enable_metrics()``: counter + latency histogram per
             retrieve, one extra ``block_until_ready``
  tracing    a live ``Tracer``: one ``retrieve`` span per call around
             the same compiled callable (stage times come from the
             device trace's ``warp.*`` scopes, not from host spans)

Arms run over the adaptive ragged plan (the serving configuration) on
the ``nfcorpus_like`` tier. ``run(micro=True)`` is the tier-1 smoke
shape. Snapshotted to BENCH_obs.json by ``benchmarks.run``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, get_setup, time_fn
from repro import obs
from repro.core import Retriever, WarpSearchConfig

TIER = "nfcorpus_like"
# Ragged adaptive plan: the serving configuration, with its host-side
# rung pick inside the dispatch.
CFG = WarpSearchConfig(nprobe=8, k=10, t_prime=400, k_impute=32,
                      layout="ragged")

# Structured per-arm summaries for BENCH_obs.json
# (benchmarks.run.write_obs_snapshot).
SUMMARY: dict = {}


def run(micro: bool = False) -> None:
    _, index, q, qmask, _ = get_setup(TIER)
    retriever = Retriever.from_index(index)
    plan = retriever.plan(CFG)
    q0 = jnp.asarray(q[0], jnp.float32)
    m0 = jnp.asarray(qmask[0], bool)

    warmup, iters = (2, 5) if micro else (3, 15)
    obs.disable_all()
    try:
        # Floor: the compiled callable itself, no dispatch layer at all.
        t_no_obs = time_fn(
            plan._single, plan._index, q0, m0, warmup=warmup, iters=iters
        )
        # Default path every benchmark/test runs: obs disabled.
        t_disabled = time_fn(
            plan.retrieve, q0, m0, warmup=warmup, iters=iters
        )
        # Metrics-only: counters + retrieve-latency histogram.
        reg = obs.enable_metrics(obs.MetricsRegistry())
        t_metrics = time_fn(plan.retrieve, q0, m0, warmup=warmup, iters=iters)
        n_retrieves = int(
            reg.counter("warp_retrieves_total", kind="single").value
        )
        obs.disable_metrics()
        # Tracing: the same callable under one retrieve span.
        tracer = obs.set_tracer(obs.Tracer())
        t_tracing = time_fn(plan.retrieve, q0, m0, warmup=warmup, iters=iters)
        n_spans = len(tracer.events())
    finally:
        obs.disable_all()

    assert n_retrieves == warmup + iters, n_retrieves
    assert n_spans > 0, "tracing arm recorded no spans"

    arms = {
        "no_obs": t_no_obs,
        "disabled": t_disabled,
        "metrics": t_metrics,
        "tracing": t_tracing,
    }
    SUMMARY.clear()
    SUMMARY["tier"] = TIER
    SUMMARY["iters"] = iters
    for arm, t in arms.items():
        over = t / max(t_no_obs, 1e-12) - 1.0
        emit(f"obs/{arm}", t, f"overhead={over:+.3f}")
        SUMMARY[arm] = {
            "us_per_call": round(t * 1e6, 1),
            "overhead_frac": round(over, 4),
        }

    # The structural claim: the disabled default costs (approximately)
    # nothing. CPU wall-clock is noisy, so the smoke bound is loose; the
    # committed BENCH_obs.json records the measured margin (<2% on the
    # snapshot run).
    assert t_disabled <= 1.25 * t_no_obs, (
        f"disabled-obs dispatch overhead too high: "
        f"{t_disabled * 1e6:.1f}us vs {t_no_obs * 1e6:.1f}us"
    )
    # Tracing records one retrieve span per call and nothing else.
    names = {s.name for s in tracer.events()}
    assert names == {"retrieve"}, names


if __name__ == "__main__":
    run()
