"""The benchmark's yardstick on the CPU: counts, generators, the index
synthesizer, the comparison, and the data-driven layout."""

from __future__ import annotations

import hashlib
import json
import re

import jax
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


def _shards(config, seed, n_shards=1):
    """A corpus of ``n_shards`` shards, all on the one CPU device."""
    return synth.make_shards(dict(config, shards=n_shards), seed, jax.devices()[:1] * n_shards)


@pytest.fixture(scope="module")
def tiny_index():
    config = _tiny_config()
    shards = _shards(config, 2**31 + 77)
    return config, shards, synth.warp_index(config, shards[0], _Index)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_counts_match_a_hand_count(n_shards):
    config = _tiny_config()
    shards = _shards(config, 2**31 + 77, n_shards)
    qs, ms = synth.make_queries(config, shards, 5, 3, stream=0, active=6)
    got = reference.probe_tokens(shards, config, qs, ms, chunk=2)
    want = []
    for q, m in zip(qs, ms):
        total = 0
        for sh in shards:
            cent = np.asarray(sh.centroids).reshape(-1, config["dim"])
            for tok in q[m]:
                top = np.argsort(-(cent @ tok), kind="stable")[: config["nprobe"]]
                total += int(sh.host_sizes[top].sum())
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


@pytest.mark.parametrize("n_shards", [1, 4])
def test_index_synthesizer_writes_valid_csr(n_shards):
    config = _tiny_config()
    shards = _shards(config, 2**31 + 77, n_shards)
    n, c = config["n_tokens"] // n_shards, config["n_centroids"] // n_shards
    lead = () if n_shards == 1 else (1,)
    for s, sh in enumerate(shards):
        offs = np.asarray(sh.offsets).reshape(-1)
        sz = np.asarray(sh.sizes).reshape(-1)
        assert offs[0] == 0 and offs[-1] == n and np.array_equal(np.diff(offs), sz) and len(sz) == c
        assert np.array_equal(sz, sh.host_sizes) and sh.cap == sz.max() and (sz > 0).all()
        # Every shard deals the one multiset of sizes, in an order of its own.
        assert np.array_equal(np.sort(sz), np.sort(shards[0].host_sizes))
        assert s == 0 or not np.array_equal(sz, shards[0].host_sizes)
        # Doc ids are local to a contiguous range of n_docs // shards (+1).
        assert sh.doc_start == s * config["n_docs"] // n_shards
        assert sh.n_docs in (config["n_docs"] // n_shards, -(-config["n_docs"] // n_shards))
        docs = np.asarray(sh.doc_ids)
        assert docs.shape == lead + (n,) and docs.min() >= 0 and docs.max() < sh.n_docs
        assert sh.codes.shape == lead + (n, 64) and sh.codes.dtype == jnp.uint8
        cent = np.asarray(sh.centroids)
        assert cent.shape == lead + (c, 128) and np.allclose(np.linalg.norm(cent, axis=-1), 1.0, atol=1e-5)
    assert sum(sh.n_docs for sh in shards) == config["n_docs"]
    if n_shards > 1:
        assert not np.array_equal(np.asarray(shards[1].codes), np.asarray(shards[0].codes))
    # Every seed deals the same multiset of sizes.
    other = _shards(config, 3, n_shards)
    assert np.array_equal(np.sort(other[0].host_sizes), np.sort(shards[0].host_sizes))
    assert other[0].cap == shards[0].cap


def test_one_shard_is_the_index_the_benchmark_always_made(tiny_index):
    """Bit for bit the arrays (and queries) the one-chip benchmark made
    before corpora had shards, for the same seed: their SHA-256."""
    config, shards, index = tiny_index
    h = hashlib.sha256()
    for name in ("centroids", "packed_codes", "token_doc_ids", "cluster_offsets", "cluster_sizes",
                 "bucket_weights", "bucket_cutoffs"):
        h.update(np.asarray(getattr(index, name)).tobytes())
    assert h.hexdigest() == "9ac0e4163224655a1f9ae478f675578d2fae91f4699a669ee65124d136ca0b31"
    assert index.cap == shards[0].cap and index.n_docs == config["n_docs"] and index.n_tokens == config["n_tokens"]
    q, _ = synth.make_queries(config, shards, 5, 3, stream=0, active=6)
    assert hashlib.sha256(q.tobytes()).hexdigest() == (
        "28bfe7f7c5d5ac0e0cd8db8e9119e0239cd0af3fa593d600a682a0ae6f276a1f"
    )


def test_the_sharded_reference_of_one_shard_is_the_reference(tiny_index):
    """The sharded reference's stages, run apart, give what the one-shard
    program gives, on one shard."""
    config, shards, _ = tiny_index
    qs, ms = synth.make_queries(config, shards, 8, 4, stream=0, active=7)
    static = dict(
        nprobe=config["nprobe"], k=config["k"], k_impute=config["k_impute"], t_prime=config["t_prime"],
        cap=shards[0].cap, nbits=config["nbits"], dtype=jnp.float32,
    )
    sh = shards[0]
    for q, m in zip(qs, ms):
        served = np.arange(config["k"], dtype=np.int32) * 7 - 1
        whole = reference.reference_query(
            sh.centroids, sh.codes, sh.doc_ids, sh.offsets, sh.sizes, sh.weights,
            jnp.asarray(q), jnp.asarray(m), jnp.asarray(served), n_docs=sh.n_docs, **static,
        )
        staged = reference.sharded_query(shards, q, m, served, **static)
        for a, b in zip(whole, staged):
            np.testing.assert_array_equal(np.asarray(a), b)


def test_generators_are_deterministic(tiny_index):
    config, shards, _ = tiny_index
    a = synth.make_queries(config, shards, 9, 4, stream=0, active=8)
    b = synth.make_queries(config, shards, 9, 4, stream=0, active=8)
    c = synth.make_queries(config, shards, 10, 4, stream=0, active=8)
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


@pytest.mark.parametrize(
    "shards,chips,tweak",
    [(4, 1, {}), (None, 4, {}), (4, 4, {"n_centroids": 30}), (4, 4, {"n_tokens": 3001}), (0, 0, {})],
)
def test_a_cell_takes_one_chip_per_shard(tmp_path, shards, chips, tweak):
    """``shards`` splits ``n_tokens`` and ``n_centroids`` evenly, one
    shard per chip; a cell on another number of chips is refused."""
    cell = tiny_cell(tmp_path, "offline", shards=4)
    assert cell.chips == 4 and cell.config["shards"] == 4
    config_file = tmp_path / "configs" / "tiny.json"
    config = dict(json.loads(config_file.read_text()), shards=shards, **tweak)
    if shards is None:
        del config["shards"]
    config_file.write_text(json.dumps(config))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        w["chips"] = chips
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell(tmp_path, "tiny.offline")
