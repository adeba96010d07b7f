"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only latency,quality,...]

Prints ``name,us_per_call,derived`` CSV lines. Wall-clock numbers are
single-core CPU (relative comparisons only); TPU roofline numbers come
from bench_roofline over the dry-run artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import time

# Default suite order. Dataset tiers (benchmarks.common.SETUPS) include
# the Zipf-skewed "zipf_like" tier: the parity suite asserts the
# query-adaptive ragged bucket undercuts the static bound there, and the
# latency suite records the bucket ladder + chosen bucket per tier in the
# BENCH_latency.json plan snapshots. "autotune" runs before "latency" so
# the tile table it installs in-process steers the latency suite's plans
# (their snapshots then record tile_source="autotune").
SUITES = ["parity", "index_size", "quality", "autotune", "latency", "serving",
          "obs", "scaling", "roofline"]

SNAPSHOT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_latency.json"
)
INDEX_SIZE_SNAPSHOT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_index_size.json"
)
SERVING_SNAPSHOT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_serving.json"
)
OBS_SNAPSHOT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_obs.json"
)


def write_obs_snapshot(path: str = OBS_SNAPSHOT_PATH) -> None:
    """Persist the observability-overhead arms (no_obs / disabled /
    metrics / tracing) so instrumentation cost regressions show up in
    diffs — the disabled arm's margin is the suite's acceptance bound."""
    from benchmarks.bench_obs import SUMMARY
    from benchmarks.common import BENCH_SCHEMA_VERSION, RECORDS

    rows = [r for r in RECORDS if r["name"].startswith("obs/")]
    if not rows:
        return
    snap = {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "generated_unix": int(time.time()),
        "metrics": rows,
        "arms": SUMMARY,
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    print(f"bench/obs/snapshot,0.0,{os.path.abspath(path)}", flush=True)


def write_serving_snapshot(path: str = SERVING_SNAPSHOT_PATH) -> None:
    """Persist the serving suite's metrics plus its structured per-arm
    summaries (QPS, latency percentiles, cache hit rate, shed fraction,
    rung occupancy) so throughput regressions show up in diffs."""
    from benchmarks.bench_serving import SUMMARY
    from benchmarks.common import BENCH_SCHEMA_VERSION, RECORDS

    rows = [r for r in RECORDS if r["name"].startswith("serving/")]
    if not rows:
        return
    snap = {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "generated_unix": int(time.time()),
        "metrics": rows,
        "arms": SUMMARY,
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    print(f"bench/serving/snapshot,0.0,{os.path.abspath(path)}", flush=True)


def write_index_size_snapshot(path: str = INDEX_SIZE_SNAPSHOT_PATH) -> None:
    """Persist the measured on-disk index footprint (per-component bytes
    from the store manifest) so size regressions show up in diffs."""
    from benchmarks.common import BENCH_SCHEMA_VERSION, RECORDS

    rows = [r for r in RECORDS if r["name"].startswith("index_size/")]
    if not rows:
        return
    snap = {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "generated_unix": int(time.time()),
        "metrics": rows,
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    print(f"bench/index_size/snapshot,0.0,{os.path.abspath(path)}", flush=True)


def write_latency_snapshot(path: str = SNAPSHOT_PATH) -> None:
    """Persist the latency suite's emitted metrics so later PRs have a perf
    trajectory to diff against (only rows under latency/), together with the
    resolved SearchPlans (strategies, t', k_impute, geometry) that produced
    them — a wall-clock number without its plan is not reproducible."""
    from benchmarks.common import BENCH_SCHEMA_VERSION, PLANS, RECORDS

    rows = [r for r in RECORDS if r["name"].startswith("latency/")]
    if not rows:
        return
    snap = {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "generated_unix": int(time.time()),
        "metrics": rows,
        "search_plans": PLANS,
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    print(f"bench/latency/snapshot,0.0,{os.path.abspath(path)}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    args = ap.parse_args()
    wanted = args.only.split(",") if args.only else SUITES

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    print("name,us_per_call,derived")
    for name in wanted:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"bench/{name}/ERROR,0.0,{type(e).__name__}: {e}", flush=True)
            raise
        print(f"bench/{name}/wall,{(time.perf_counter() - t0) * 1e6:.0f},suite_total",
              flush=True)
        if name == "latency":
            write_latency_snapshot()
        if name == "index_size":
            write_index_size_snapshot()
        if name == "serving":
            write_serving_snapshot()
        if name == "obs":
            write_obs_snapshot()


if __name__ == "__main__":
    main()
