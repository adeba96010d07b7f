"""A cell small enough for the CPU, for the benchmark's own tests: the
Lifestyle configuration's plan, serving policy, precision and synthesis,
at a few thousand tokens."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from tpubench import spec

TINY = {
    "n_tokens": 3000, "n_docs": 150, "n_centroids": 32, "nprobe": 4, "k": 10,
    "k_impute": 8, "t_prime": 54, "query_maxlen": 8, "knee_qps": 200.0,
}


def tiny_cell(root: Path, loop: str = "offline", **overrides) -> spec.Cell:
    """Write a one-config benchmark under ``root`` and resolve its
    ``loop`` cell (``steady`` or ``offline``) with 8 active tokens, on as
    many chips as the configuration has ``shards``."""
    config = json.loads((spec.HERE / "configs" / "lotte-lifestyle.json").read_text())
    config.update(TINY, name="tiny", **overrides)
    (root / "configs").mkdir(parents=True, exist_ok=True)
    (root / "configs" / "tiny.json").write_text(json.dumps(config))
    bench = json.loads((spec.HERE.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "configs/tiny.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": config.get("shards", 1),
         "why": "test"}
        for t in ("steady", "offline")
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({"tiny." + w.split(".")[-1] for w in m["workloads"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, f"tiny.{loop}")
    return dataclasses.replace(cell, traffic=dict(cell.traffic, active_tokens=8))
