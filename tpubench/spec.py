"""``BENCHMARK.json`` and the data files it names, resolved for one cell.

The harness is driven by data. A cell names a configuration and a traffic
mix; the configuration's file is the one its ``configs`` entry gives, the
mix is ``traffic/<name>.json`` beside this module, and each per-layer
metric is read by ``metrics/<name>.py``, or, where no such file is
there, by the reader of the quantity its name starts with: the part
before its first dot. ``xla_ms.steady`` (moving ``p50_ms``) and
``xla_ms.offline`` (moving ``qps``) are one quantity, split by the
end-to-end metric it moves, read by ``metrics/xla_ms.py``. A
configuration may state ``shards``: its corpus is then document-sharded,
one shard per chip, and a cell of it asks for as many chips. A later
change adds a configuration, a mix or a metric by adding files and
entries, never by editing one that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

HERE = Path(__file__).resolve().parent


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None
    bound: float | None = None
    layer: str | None = None
    moves: str | None = None

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    readers: dict[str, Callable[[Any], float | None]]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(
        name=entry["name"], unit=entry["unit"], better=entry["better"],
        source=entry["source"], workloads=None if wl is None else tuple(wl),
        bound=entry.get("bound"), layer=entry.get("layer"), moves=entry.get("moves"),
    )


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not valid JSON: {e}") from e


def reader_path(bench_dir: Path, name: str) -> Path:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, else
    the one of its quantity, ``metrics/<name up to its first dot>.py``."""
    own = bench_dir / "metrics" / f"{name}.py"
    return own if own.is_file() else bench_dir / "metrics" / f"{name.split('.')[0]}.py"


def load_reader(path: Path) -> Callable[[Any], float | None]:
    """The ``read`` function of a per-layer metric's reader module."""
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric: {path}")
    spec = importlib.util.spec_from_file_location(f"tpubench_reader_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    read = getattr(mod, "read", None)
    if not callable(read):
        raise SpecError(f"{path} defines no read(run) function")
    return read


def _check_shards(cell: str, chips: int, config: dict) -> None:
    """A configuration's ``shards`` (one when absent) are document shards,
    one per chip; ``n_tokens`` and ``n_centroids`` are the whole
    corpus's and split evenly over them."""
    shards = config.get("shards", 1)
    if not isinstance(shards, int) or shards < 1:
        raise SpecError(f"configuration {config.get('name')!r}: shards must be a positive integer, got {shards!r}")
    if chips != shards:
        raise SpecError(f"workload {cell} asks for {chips} chip(s) for {shards} shard(s); one shard per chip")
    for key in ("n_tokens", "n_centroids"):
        if shards > 1 and config[key] % shards:
            raise SpecError(f"{key} {config[key]} does not split over {shards} shards")


def load_cell(root: Path, name: str, bench_dir: Path = HERE) -> Cell:
    """Resolve cell ``name`` of ``root/BENCHMARK.json``. Traffic mixes and
    readers are looked up under ``bench_dir``; the configuration file is
    the path its entry gives, relative to ``root``."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name} names unknown config {w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    _check_shards(name, int(w["chips"]), config)
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in map(_metric, bench["end_to_end"]) if m.reported_in(name))
    per_layer = tuple(m for m in map(_metric, bench["per_layer"]) if m.reported_in(name))
    readers = {m.name: load_reader(reader_path(bench_dir, m.name)) for m in per_layer}
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer, readers=readers,
    )
