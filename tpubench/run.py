#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python tpubench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run makes the cell's index on the device from ``--seed``, stands up
one ``RetrievalServer`` with the configuration's plan and the kernel
executor, warms the one batch program its traffic uses, then drives the
traffic for ``--seconds`` and checks a sample of the replies against the
plain reference (``reference.py``, ``compare.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared beside its limit, also the last lines
of standard error.

No result line and a non-zero exit: no TPU (or fewer chips than the cell
asks for), a plan demoted to the reference executor, a retrieve program
without a compiled Pallas kernel, a benchmark file missing or the
program not importable.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from tpubench import compare, spec, stats, traffic  # noqa: E402
from tpubench.checks import RunFailure  # noqa: E402

# Requests whose replies each run holds against the reference.
CHECK_REQUESTS = 64


def log(*parts) -> None:
    print("tpubench:", *parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader gets (``metrics/<name>.py``)."""

    cell: spec.Cell
    window: traffic.Window
    counters: dict  # server counters over the window
    trace: object | None  # trace_reduce.TraceSummary
    work: dict  # roofline inputs: candidate tokens served, the chip's peaks


def served_in_window(w: traffic.Window) -> float:
    """Replies completed inside the window, with the batch in flight at
    its close counted for the share of its step that fell inside."""
    n = 0.0
    for s0, s1, got in w.steps:
        if s1 <= w.t1:
            n += len(got)
        elif s0 < w.t1:
            n += len(got) * (w.t1 - s0) / (s1 - s0)
    return n


def end_to_end(name: str, window: traffic.Window, seconds: float, setup_s: float, peak_bytes: int):
    w = window
    lat_ms = (w.done - w.due)[w.counted & ~np.isnan(w.done)] * 1e3
    if name == "p50_ms":
        return stats.percentile(lat_ms, 50)
    if name == "p95_ms":
        return stats.percentile(lat_ms, 95)
    if name == "qps":
        return served_in_window(w) / seconds
    if name == "peak_hbm_gb":
        return peak_bytes / 1e9
    if name == "setup_s":
        return setup_s
    raise spec.SpecError(f"the harness computes no end-to-end metric {name!r}")


def make_server(cell: spec.Cell, index):
    from repro.core import Retriever, WarpSearchConfig
    from repro.serving import BatchPolicy, RetrievalServer

    c = cell.config
    cfg = WarpSearchConfig(
        nprobe=c["nprobe"], k=c["k"], k_impute=c["k_impute"], t_prime=c["t_prime"],
        executor="kernel", **c["plan"],
    )
    srv = c["serving"]
    return RetrievalServer(
        Retriever.from_index(index), cfg,
        BatchPolicy(max_batch=srv["max_batch"], max_wait_s=srv["max_wait_s"]),
        cache_size=srv["cache_size"],
    )


def drive(cell: spec.Cell, server, qs, ms, seconds: float, trace: bool) -> traffic.Window:
    t = cell.traffic
    if t["loop"] == "open":
        gaps = traffic.arrival_gaps(cell.config, t, len(qs))
        return traffic.drive_open(server, qs, ms, gaps, seconds, trace=trace)
    if t["loop"] == "closed":
        return traffic.drive_closed(server, qs, ms, t["outstanding"], seconds, trace=trace)
    raise spec.SpecError(f"traffic loop {t['loop']!r} is neither 'open' nor 'closed'")


def check_replies(cell: spec.Cell, index, window: traffic.Window, qs, ms, seed: int) -> dict:
    from tpubench import reference

    done = np.flatnonzero(window.counted & ~np.isnan(window.done))
    picked = compare.sample(done, CHECK_REQUESTS, np.random.default_rng([4, seed]))
    replies = [window.replies[j] for j in picked]
    queries = [(qs[window.pool_idx[j]], ms[window.pool_idx[j]]) for j in picked]
    refs = reference.run_reference(index, cell.config, queries, replies)
    return compare.checks(cell.config, compare.score_gap(replies, refs), window.n_failed)


def candidate_tokens(cell: spec.Cell, index, window: traffic.Window, qs, ms) -> int:
    from tpubench import reference

    served = [j for _, _, got in window.steps for j in got]
    pool = np.unique(window.pool_idx[served]) if served else np.zeros(0, np.int64)
    per_query = reference.probe_tokens(index, cell.config, qs[pool], ms[pool])
    counts = dict(zip(pool.tolist(), per_query.tolist()))
    return int(sum(counts[int(window.pool_idx[j])] for j in served))


def counter_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def peak_hbm_bytes(dev) -> tuple[int, int, int]:
    """The most HBM the run needs at once on ``dev``: the allocator's peak
    of live buffers (the index, queries, replies) plus the largest scratch
    space of a compiled program loaded on the device. The allocator does
    not count a program's scratch (the gathered codes, the sort's buffers,
    relayout copies), which the runtime sets aside for each execution.
    Returns (total, buffers, scratch)."""
    buffers = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    scratch = max(
        (e.get_compiled_memory_stats().temp_size_in_bytes for e in dev.client.live_executables()),
        default=0,
    )
    return buffers + scratch, buffers, scratch


class Pauses:
    """What held up the loop inside the window, for its log line: garbage
    collections and any compilation (none should run there)."""

    def __init__(self):
        self.gc_s: list = []
        self.compiles: list = []
        self._gc_t0 = None

    def __enter__(self):
        import jax

        gc.callbacks.append(self._gc)
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            self.gc_s.append(time.monotonic() - self._gc_t0)
            self._gc_t0 = None

    def _event(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.compiles.append(f"{event.rsplit('/', 1)[-1]} {kw.get('fun_name', '?')} {duration:.3f} s")


def window_health(w: traffic.Window, pauses: Pauses) -> str:
    """The longest step, the longest time between two steps, and what the
    loop spent on collection and compilation, in one line of the log."""
    if not w.steps:
        return "no step served a request"
    spans = np.array([(s0, s1) for s0, s1, _ in w.steps])
    dur = spans[:, 1] - spans[:, 0]
    gaps = spans[1:, 0] - spans[:-1, 1] if len(spans) > 1 else np.zeros(1)
    i, g = int(np.argmax(dur)), int(np.argmax(gaps))
    gc_max = max(pauses.gc_s, default=0.0)
    return (
        f"longest step {dur[i] * 1e3:.1f} ms at +{spans[i, 0] - w.t0:.2f} s, "
        f"median {np.median(dur) * 1e3:.1f} ms; longest time between steps {gaps[g] * 1e3:.1f} ms "
        f"at +{spans[g, 1] - w.t0:.2f} s; {len(pauses.gc_s)} collections, longest {gc_max * 1e3:.1f} ms; "
        f"compilations in the window: {pauses.compiles or 'none'}"
    )


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *, chip: bool = True) -> dict:
    import jax

    from repro.core import WarpIndex
    from tpubench import checks, synth

    devices = checks.require_tpu(cell.chips) if chip else jax.devices()[: cell.chips]
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform})")
    c, t = cell.config, cell.traffic
    index, sizes = synth.make_index(c, seed, WarpIndex)
    n_pool = traffic.pool_size(c, t, seconds)
    qs, ms = synth.make_queries(c, index, sizes, seed, n_pool, stream=0, active=t["active_tokens"])
    max_batch = c["serving"]["max_batch"]
    warm_q, warm_m = synth.make_queries(c, index, sizes, seed, 2 * max_batch, stream=1, active=t["active_tokens"])
    log(f"index: {c['n_tokens']} tokens, {c['n_docs']} docs, {c['n_centroids']} centroids, "
        f"cap {index.cap}; pool of {n_pool} queries; {time.monotonic() - T_START:.1f} s")
    server = make_server(cell, index)
    checks.check_no_fallback(server)
    if chip:
        checks.check_kernel_in_program(server, warm_q[:max_batch], warm_m[:max_batch])
    for i in range(2):  # the first batch compiles (or loads) the program
        for j in range(max_batch):
            server.submit(warm_q[i * max_batch + j], warm_m[i * max_batch + j])
        server.drain()
    checks.check_no_fallback(server)
    before = dict(server.stats)
    setup_s = time.monotonic() - T_START
    log(f"setup: {setup_s:.3f} s; plan {server.plan.describe()}")

    trace_dir = tempfile.mkdtemp(prefix="tpubench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    with Pauses() as pauses:
        window = drive(cell, server, qs, ms, seconds, trace)
    if trace:
        jax.profiler.stop_trace()
    peak_bytes, buffers, scratch = peak_hbm_bytes(dev)
    checks.check_no_fallback(server)
    counters = counter_delta(before, dict(server.stats))
    late = (window.sent - window.due)[window.counted] * 1e3
    log(f"window: {window.n_counted} requests, {len(window.steps)} batches, {counters}; "
        f"generator late p50 {stats.percentile(late, 50):.3f} ms, max {np.max(late, initial=0):.3f} ms")
    log(f"window health: {window_health(window, pauses)}")
    log(f"HBM: {peak_bytes} bytes at the peak = {buffers} of live buffers + {scratch} of program scratch")
    del server
    gc.collect()

    checked = check_replies(cell, index, window, qs, ms, seed)
    result = {
        "correct": compare.passed(checked),
        "attempted": window.n_counted,
        "failed": window.n_failed,
        "metrics": {},
        "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if not trace:
        for m in cell.end_to_end:
            v = end_to_end(m.name, window, seconds, setup_s, peak_bytes)
            result["metrics"][m.name] = {"value": v, "unit": m.unit}
    else:
        from tpubench import roofline, trace_reduce

        summary = _reduce_trace(trace_dir)
        work = {
            "candidate_tokens": candidate_tokens(cell, index, window, qs, ms),
            "peaks": roofline.peaks(dev.device_kind) if chip else None,
        }
        view = RunView(cell, window, counters, summary, work)
        for m in cell.per_layer:
            v = cell.readers[m.name](view)
            if v is not None:
                result["metrics"][m.name] = {"value": v, "unit": m.unit}
        if summary is not None:
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = trace_reduce.breakdown(summary)
    result["checks"] = checked
    return result


def _reduce_trace(trace_dir: str):
    from tpubench import trace_reduce

    try:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        return trace_reduce.reduce(trace_reduce.load(str(files[-1]))) if files else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.load_cell(ROOT, args.workload)
        from repro.launch.compile_cache import setup_compile_cache
    except (spec.SpecError, ImportError) as e:
        log(f"cannot run: {e}")
        return 2
    import jax

    cache = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except RunFailure as e:
        log(f"failed run: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
