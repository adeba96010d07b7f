"""The benchmark's yardstick on the CPU: counts, generators, the index
synthesizer, the comparison, and the data-driven layout."""

from __future__ import annotations

import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import compare, reference, roofline, spec, stats, synth, traffic
from tpubench.tiny import TINY, tiny_cell


def _tiny_config():
    config = json.loads((spec.HERE / "configs" / "lotte-lifestyle.json").read_text())
    config.update(TINY)
    return config


class _Index:
    """The fields of the program's ``WarpIndex`` the benchmark reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


@pytest.fixture(scope="module")
def tiny_index():
    config = _tiny_config()
    index, sizes = synth.make_index(config, 2**31 + 77, _Index)
    return config, index, sizes


def test_counts_match_a_hand_count(tiny_index):
    config, index, sizes = tiny_index
    qs, ms = synth.make_queries(config, index, sizes, 5, 3, stream=0, active=6)
    got = reference.probe_tokens(index, config, qs, ms, chunk=2)
    cent = np.asarray(index.centroids)
    want = []
    for q, m in zip(qs, ms):
        total = 0
        for tok in q[m]:
            s = cent @ tok
            top = np.argsort(-s, kind="stable")[: config["nprobe"]]
            total += int(sizes[top].sum())
        want.append(total)
    assert got.tolist() == want
    n = want[0]
    assert roofline.candidate_bytes(n, 128, 4) == n * (64 + 4)
    assert roofline.candidate_ops(n, 128) == n * 256
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time(68e9, 256e9, peak)
    assert bound == "memory" and t == pytest.approx(68e9 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_index_synthesizer_writes_valid_csr(tiny_index):
    config, index, sizes = tiny_index
    n = config["n_tokens"]
    offs = np.asarray(index.cluster_offsets)
    sz = np.asarray(index.cluster_sizes)
    assert offs[0] == 0 and offs[-1] == n and np.array_equal(np.diff(offs), sz)
    assert np.array_equal(sz, sizes) and index.cap == sz.max() and (sz > 0).all()
    docs = np.asarray(index.token_doc_ids)
    assert docs.shape == (n,) and docs.min() >= 0 and docs.max() < config["n_docs"]
    assert index.packed_codes.shape == (n, 64) and index.packed_codes.dtype == jnp.uint8
    assert np.allclose(np.linalg.norm(np.asarray(index.centroids), axis=1), 1.0, atol=1e-5)
    # Every seed deals the same multiset of sizes.
    other, _ = synth.make_index(config, 3, _Index)
    assert np.array_equal(np.sort(np.asarray(other.cluster_sizes)), np.sort(sz))
    assert other.cap == index.cap


def test_generators_are_deterministic(tiny_index):
    config, index, sizes = tiny_index
    a = synth.make_queries(config, index, sizes, 9, 4, stream=0, active=8)
    b = synth.make_queries(config, index, sizes, 9, 4, stream=0, active=8)
    c = synth.make_queries(config, index, sizes, 10, 4, stream=0, active=8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    g1 = traffic.poisson_gaps(50.0, 1000, np.random.default_rng([3, 2**33 + 1]))
    g2 = traffic.poisson_gaps(50.0, 1000, np.random.default_rng([3, 2**33 + 1]))
    g3 = traffic.poisson_gaps(50.0, 1000, np.random.default_rng([3, 7]))
    assert np.array_equal(g1, g2) and not np.array_equal(g1, g3)
    assert np.array_equal(np.sort(g1), np.sort(g3))
    assert 1000 / g1.sum() == pytest.approx(50.0, rel=0.01)
    steady = json.loads((spec.HERE / "traffic" / "steady.json").read_text())
    a1 = traffic.arrival_gaps(config, steady, 500)
    assert np.array_equal(a1, traffic.arrival_gaps(config, steady, 500))
    assert not np.array_equal(a1, traffic.arrival_gaps(config, dict(steady, order_seed=1), 500))


def test_cluster_sizes_follow_the_config():
    config = json.loads((spec.HERE / "configs" / "lotte-lifestyle.json").read_text())
    sizes = synth.cluster_sizes(config)
    assert sizes.sum() == config["n_tokens"] and len(sizes) == config["n_centroids"]
    assert 300 < sizes.max() < 450


def test_comparison_is_tie_aware():
    ref_s = np.array([10.0, 9.0, 9.0, 8.0], np.float32)
    at = np.array([10.0, 9.0, 9.0, 8.0], np.float32)
    # Two docs of equal score in either order.
    assert compare.reply_gap(ref_s, np.array([1, 3, 2, 4]), ref_s, at) == 0.0
    # A served score off by a little.
    off = ref_s.copy()
    off[3] = 8.0008
    assert compare.reply_gap(off, np.array([1, 2, 3, 4]), ref_s, at) == pytest.approx(1e-4, rel=1e-3)
    # A wrong doc: the reference scores it lower than the server says.
    wrong_at = at.copy()
    wrong_at[1] = 5.0
    assert compare.reply_gap(ref_s, np.array([1, 7, 3, 4]), ref_s, wrong_at) > 0.4
    # A doc the reference never scores, a duplicate, a missing answer.
    missing = at.copy()
    missing[2] = -np.inf
    assert compare.reply_gap(ref_s, np.array([1, 2, 9, 4]), ref_s, missing) == np.inf
    assert compare.reply_gap(ref_s, np.array([1, 2, 2, 4]), ref_s, at) == np.inf
    assert compare.reply_gap(ref_s[:3], np.array([1, 2, 3]), ref_s, at) == np.inf
    checked = compare.checks({"limits": {"score_gap": 1e-5}}, 1e-6, 0)
    assert compare.passed(checked)
    assert not compare.passed(compare.checks({"limits": {"score_gap": 1e-5}}, 1e-6, 1))


def test_statistics():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert np.isnan(stats.percentile([], 95))
    assert stats.percentile([3.0, 1.0, 2.0], 95) == pytest.approx(2.9)


def test_a_later_change_adds_a_config_mix_and_reader_as_files(tmp_path):
    """A new configuration, traffic mix and per-layer metric are found by
    name, with no edit to a file the benchmark has."""
    bench_dir = tmp_path / "bench"
    (bench_dir / "traffic").mkdir(parents=True)
    (bench_dir / "metrics").mkdir()
    (bench_dir / "configs").mkdir()
    (bench_dir / "traffic" / "bursty.json").write_text(json.dumps({"loop": "open", "load_of_knee": 1.6}))
    (bench_dir / "metrics" / "burst_depth.steady.py").write_text("def read(run):\n    return 3.0\n")
    (bench_dir / "metrics" / "burst_depth.py").write_text("def read(run):\n    return 4.0\n")
    (bench_dir / "configs" / "skew.json").write_text(json.dumps({"name": "skew", "n_tokens": 10}))
    bench = json.loads((spec.HERE.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "skew", "source": "test", "file": "bench/configs/skew.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "skew.bursty", "config": "skew", "traffic": "bursty", "chips": 1, "why": "t"})
    bench["per_layer"] = [
        {"name": "burst_depth.steady", "unit": "req", "better": "lower", "source": "host_clock",
         "layer": "serving", "moves": "p95_ms", "workloads": ["skew.bursty"]},
        {"name": "burst_depth.offline", "unit": "req", "better": "lower", "source": "host_clock",
         "layer": "serving", "moves": "qps", "workloads": ["skew.bursty"]},
    ]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(tmp_path, "skew.bursty", bench_dir)
    assert cell.config["n_tokens"] == 10 and cell.traffic["load_of_knee"] == 1.6
    assert [m.name for m in cell.per_layer] == ["burst_depth.steady", "burst_depth.offline"]
    # A reader of its own where there is one, else the quantity's.
    assert cell.readers["burst_depth.steady"](None) == 3.0
    assert cell.readers["burst_depth.offline"](None) == 4.0
    assert {m.name for m in cell.end_to_end} == {"peak_hbm_gb", "setup_s"}
    with pytest.raises(spec.SpecError):
        spec.load_cell(tmp_path, "no.such.cell", bench_dir)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_its_contract():
    root = spec.HERE.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"] and "\t" not in c["why"]
        assert (root / c["file"]).is_file() and c["file"].startswith("tpubench/")
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and NAME.match(w["name"])
        assert (spec.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        assert spec.reader_path(spec.HERE, m["name"]).is_file()
        for w in m["workloads"]:
            assert e2e[m["moves"]].get("workloads") is None or w in e2e[m["moves"]]["workloads"]
    for name in cells:
        cell = spec.load_cell(root, name)
        assert {m.name for m in cell.end_to_end} > {"setup_s"} and cell.per_layer
