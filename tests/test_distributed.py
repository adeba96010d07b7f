"""Distributed (doc-sharded) WARP engine. Runs on however many host
devices exist — on this container that is 1, so the shard_map path is
exercised with n_shards = 1 here; the multi-device path is covered by the
subprocess test below and by launch/dryrun.py."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    IndexBuildConfig,
    WarpSearchConfig,
    build_sharded_index,
    sharded_search,
)
from repro.data import make_corpus, make_queries


def test_sharded_single_device():
    corpus = make_corpus(n_docs=200, mean_doc_len=16, seed=0)
    q, qmask, rel = make_queries(corpus, n_queries=4, seed=1)
    sidx = build_sharded_index(
        corpus.emb,
        corpus.token_doc_ids,
        corpus.n_docs,
        n_shards=len(jax.devices()),
        config=IndexBuildConfig(n_centroids=64, nbits=4, kmeans_iters=3),
    )
    cfg = WarpSearchConfig(nprobe=32, k=10, t_prime=1000, k_impute=64)
    hits = 0
    for i in range(4):
        r = sharded_search(sidx, q[i], jnp.asarray(qmask[i]), cfg)
        s = np.asarray(r.scores)
        assert np.all(np.diff(s[np.isfinite(s)]) <= 1e-6)
        hits += int(rel[i] in np.asarray(r.doc_ids))
    assert hits >= 3


def test_shard_doc_partition_covers_all_docs():
    corpus = make_corpus(n_docs=101, mean_doc_len=12, seed=3)
    sidx = build_sharded_index(
        corpus.emb,
        corpus.token_doc_ids,
        corpus.n_docs,
        n_shards=4,
        config=IndexBuildConfig(n_centroids=16, nbits=4, kmeans_iters=2),
    )
    starts = np.asarray(sidx.doc_start)
    assert starts[0] == 0
    assert np.all(np.diff(starts) >= 0)
    assert sidx.n_docs == corpus.n_docs


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import build_sharded_index, sharded_search, IndexBuildConfig, WarpSearchConfig
from repro.data import make_corpus, make_queries

corpus = make_corpus(n_docs=400, mean_doc_len=20, seed=0)
q, qmask, rel = make_queries(corpus, n_queries=6, seed=1)
sidx = build_sharded_index(corpus.emb, corpus.token_doc_ids, corpus.n_docs, 4,
                           IndexBuildConfig(n_centroids=32, nbits=4, kmeans_iters=3))
cfg = WarpSearchConfig(nprobe=16, k=10, t_prime=2000, k_impute=32)
hits = 0
for i in range(6):
    r = sharded_search(sidx, q[i], jnp.asarray(qmask[i]), cfg)
    hits += int(rel[i] in np.asarray(r.doc_ids))
assert hits >= 5, hits
print("OK", hits)
"""


@pytest.mark.slow
def test_sharded_multi_device_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import IndexBuildConfig, Retriever, WarpSearchConfig, build_sharded_index
from repro.core.distributed import stack_shards
from repro.data import make_corpus, make_queries

corpus = make_corpus(n_docs=240, mean_doc_len=14, seed=0)
sidx = build_sharded_index(corpus.emb, corpus.token_doc_ids, corpus.n_docs, 4,
                           IndexBuildConfig(n_centroids=16, kmeans_iters=2))
devs = jax.devices()
for arr in (sidx.packed_codes, sidx.centroids, sidx.doc_start):
    shards = arr.addressable_shards
    assert sorted(d.id for d in {s.device for s in shards}) == [0, 1, 2, 3]
    assert all(s.data.shape[0] == 1 for s in shards)
    assert all(s.index[0].start == s.device.id for s in shards)
q, qmask, _ = make_queries(corpus, n_queries=2, seed=1)
cfg = WarpSearchConfig(nprobe=8, k=10)
placed = Retriever.from_index(sidx).plan(cfg).retrieve(q[0], qmask[0])
# The same shards stacked on one device answer identically.
stacked = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), sidx)
again = Retriever.from_index(stacked).plan(cfg).retrieve(q[0], qmask[0])
np.testing.assert_array_equal(np.asarray(placed.doc_ids), np.asarray(again.doc_ids))
print("OK")
"""


def test_four_shard_placement_one_shard_per_device_subprocess():
    """A 4-shard build on 4 devices puts shard s on device s (never the
    whole stack on device 0) and answers like a single-device stack."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", PLACEMENT_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
