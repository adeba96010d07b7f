#!/usr/bin/env python3
"""The two readings a cell's ``score_gap`` limit is set from, on the chip.

    python tpubench/control.py --workload <cell> --seeds 101,102,... \
        --control-seeds 101,102,103 --seconds 4 --out <file.json>

For each seed, in one process: the cell's index and server, a short
window of the cell's own traffic, and the ``score_gap`` of a sample of
its replies against the plain reference, as ``run.py`` computes it (the
program's reading). For each control seed also the control's reading:
the reference itself, computed in bfloat16, put in the program's place
for the same sampled requests. The limit goes between the largest
program reading and the smallest control reading (``PERF.md``).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from tpubench import compare, run, spec, traffic  # noqa: E402


def control_gap(index, config: dict, queries) -> float:
    """The bfloat16 reference in the program's place, against the float32
    reference."""
    import jax.numpy as jnp

    from tpubench import reference

    k = config["k"]
    blank = [(None, np.full(k, -1, np.int32))] * len(queries)
    low = reference.run_reference(index, config, queries, blank, dtype=jnp.bfloat16)
    served = [(s, d) for s, d, _ in low]
    refs = reference.run_reference(index, config, queries, served)
    return compare.score_gap(served, refs)


def readings(cell: spec.Cell, seed: int, seconds: float, with_control: bool, *, chip: bool = True) -> dict:
    import jax

    from repro.core import WarpIndex
    from tpubench import checks, reference, synth

    if chip:
        checks.require_tpu(cell.chips)
    c, t = cell.config, cell.traffic
    index, sizes = synth.make_index(c, seed, WarpIndex)
    qs, ms = synth.make_queries(
        c, index, sizes, seed, traffic.pool_size(c, t, seconds), stream=0, active=t["active_tokens"]
    )
    server = run.make_server(cell, index)
    window = run.drive(cell, server, qs, ms, seconds, False)
    checks.check_no_fallback(server)
    del server
    gc.collect()
    done = np.flatnonzero(window.counted & ~np.isnan(window.done))
    picked = compare.sample(done, run.CHECK_REQUESTS, np.random.default_rng([4, seed]))
    replies = [window.replies[j] for j in picked]
    queries = [(qs[window.pool_idx[j]], ms[window.pool_idx[j]]) for j in picked]
    refs = reference.run_reference(index, c, queries, replies)
    out = {
        "seed": seed, "compared": len(picked), "failed": window.n_failed,
        "program": compare.score_gap(replies, refs),
    }
    if with_control:
        out["control"] = control_gap(index, c, queries)
    del index
    jax.clear_caches()
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        rows.append(readings(cell, seed, args.seconds, seed in control))
        print(json.dumps(rows[-1]), flush=True)
    summary = {
        "workload": cell.name,
        "program_max": max(r["program"] for r in rows),
        "control_min": min((r["control"] for r in rows if "control" in r), default=None),
        "rows": rows,
    }
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
