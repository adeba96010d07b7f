#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: the highest open-loop
rate at which the backlog does not grow over the window.

    python tpubench/sweep.py --workload <steady cell> --seed <n> \
        --seconds 10 --loads 0.6,0.8,0.9,1.0,1.1

In one process: the cell's index and server, a closed-loop window (64 in
flight) whose throughput is the first guess, then one open-loop window
at each of ``--loads`` times that throughput. A window's backlog is the
requests due but not yet answered; it grows when at the close it exceeds
its value at mid-window by more than one batch. The highest rate whose
backlog does not grow is the knee, written into the configuration's file
as ``knee_qps`` by hand, with the sweep's output in ``PERF.md``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from tpubench import run, spec, stats, traffic  # noqa: E402


def backlog(w: traffic.Window, t: float) -> int:
    return int((w.due <= t).sum() - (w.done <= t).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--loads", default="0.6,0.8,0.9,1.0,1.1")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    from repro.core import WarpIndex
    from repro.launch.compile_cache import setup_compile_cache
    from tpubench import checks, synth

    setup_compile_cache()
    checks.require_tpu(cell.chips)
    c, t = cell.config, cell.traffic
    index, sizes = synth.make_index(c, args.seed, WarpIndex)
    server = run.make_server(cell, index)
    max_batch = c["serving"]["max_batch"]
    stream = 0

    def pool(n):
        nonlocal stream
        stream += 1
        return synth.make_queries(c, index, sizes, args.seed, n, stream=stream, active=t["active_tokens"])

    wq, wm = pool(2 * max_batch)
    for i in range(2 * max_batch):
        server.submit(wq[i], wm[i])
    server.drain()
    qs, ms = pool(4096)
    w = traffic.drive_closed(server, qs, ms, 64, args.seconds)
    closed = float((w.done <= w.t1).sum()) / args.seconds
    print(json.dumps({"loop": "closed", "qps": closed}), flush=True)
    knee = None
    for load in (float(x) for x in args.loads.split(",")):
        rate = load * closed
        n = int(rate * (args.seconds + 5)) + 1
        qs, ms = pool(n)
        gaps = traffic.poisson_gaps(rate, n, np.random.default_rng([3, t["order_seed"]]))
        w = traffic.drive_open(server, qs, ms, gaps, args.seconds)
        mid, end = backlog(w, w.t0 + args.seconds / 2), backlog(w, w.t1)
        grows = end > mid + max_batch
        lat = (w.done - w.due)[w.counted & ~np.isnan(w.done)] * 1e3
        print(json.dumps({
            "loop": "open", "load": load, "rate": rate, "backlog_mid": mid, "backlog_end": end,
            "grows": grows, "p50_ms": stats.percentile(lat, 50), "p95_ms": stats.percentile(lat, 95),
        }), flush=True)
        if not grows:
            knee = rate
    print(json.dumps({"knee_qps": knee, "closed_qps": closed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
