"""A WARP index and a query pool made from ``--seed``, on the device.

No k-means runs: the benchmark measures serving, and building an index at
LoTTE size takes minutes that every run would pay. The arrays have the
shapes, dtypes and CSR layout the index build (`store/builder.py`) writes:

- cluster sizes: one fixed multiset per configuration (Gamma-distributed
  weights drawn once with the configuration's ``size_seed``, scaled to
  ``n_tokens``), dealt to the centroids in an order drawn from the run's
  seed. Every seed so has the same largest cluster, and with it the same
  compiled programs and the same work, in another place;
- centroids: unit vectors, normal in every direction;
- residual codes: uniform over the 2^nbits buckets, which is what
  quantile buckets make of any residual distribution;
- bucket weights and cutoffs: the quantile midpoints and boundaries of a
  normal residual of standard deviation ``residual_std``;
- token doc ids: uniform over the documents;
- query tokens: centroids picked in proportion to their cluster size,
  perturbed by normal noise of ``query_noise`` per dimension and
  renormalized (cosine about 0.7 to their centroid at dim 128).
"""

from __future__ import annotations

import functools
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

# Rows of codes generated per step of the on-device fill.
CHUNK_ROWS = 1 << 18


def cluster_sizes(config: dict) -> np.ndarray:
    """The configuration's multiset of cluster sizes, sorted descending."""
    s = config["synthesis"]
    n, c = config["n_tokens"], config["n_centroids"]
    w = np.random.default_rng(s["size_seed"]).gamma(s["size_gamma_shape"], size=c)
    raw = w / w.sum() * n
    sizes = np.floor(raw).astype(np.int64)
    short = n - int(sizes.sum())
    sizes[np.argsort(sizes - raw, kind="stable")[:short]] += 1
    return np.sort(sizes)[::-1]


def codec_tables(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """(bucket_weights f32[2^b], bucket_cutoffs f32[2^b - 1])."""
    nb = 1 << config["nbits"]
    nd = NormalDist(0.0, config["synthesis"]["residual_std"])
    weights = [nd.inv_cdf((i + 0.5) / nb) for i in range(nb)]
    cutoffs = [nd.inv_cdf(i / nb) for i in range(1, nb)]
    return np.asarray(weights, np.float32), np.asarray(cutoffs, np.float32)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(0)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """The host generator of one named stream of a run's seed."""
    return np.random.default_rng([stream, seed])


@functools.partial(jax.jit, static_argnames=("n_tokens", "n_docs", "n_centroids", "dim", "pb"))
def _fill(key, *, n_tokens, n_docs, n_centroids, dim, pb):
    """Centroids, codes and doc ids in one program; codes and doc ids are
    filled in chunks so no temporary is larger than one chunk."""
    k_cent, k_codes, k_docs = jax.random.split(key, 3)
    cent = jax.random.normal(k_cent, (n_centroids, dim), jnp.float32)
    cent = cent / jnp.linalg.norm(cent, axis=1, keepdims=True)
    rows = min(CHUNK_ROWS, n_tokens)
    n_chunks = -(-n_tokens // rows)

    def body(i, bufs):
        codes, docs = bufs
        # The last chunk overlaps the one before it rather than run past
        # the end; its rows are simply drawn again.
        start = jnp.minimum(i * rows, n_tokens - rows)
        bits = jax.random.bits(jax.random.fold_in(k_codes, i), (rows, pb // 4), jnp.uint32)
        chunk = jax.lax.bitcast_convert_type(bits, jnp.uint8).reshape(rows, pb)
        d = jax.random.randint(jax.random.fold_in(k_docs, i), (rows,), 0, n_docs, jnp.int32)
        return (
            jax.lax.dynamic_update_slice(codes, chunk, (start, 0)),
            jax.lax.dynamic_update_slice(docs, d, (start,)),
        )

    codes = jnp.zeros((n_tokens, pb), jnp.uint8)
    docs = jnp.zeros((n_tokens,), jnp.int32)
    codes, docs = jax.lax.fori_loop(0, n_chunks, body, (codes, docs))
    return cent, codes, docs


def make_index(config: dict, seed: int, index_type):
    """The index of one run, on the default device, as ``index_type``
    (the program's ``WarpIndex``). Also returns the host copy of the
    cluster sizes, which the roofline's byte count reads."""
    n, c, dim, nbits = (config[k] for k in ("n_tokens", "n_centroids", "dim", "nbits"))
    pb = dim * nbits // 8
    if pb % 4:
        raise ValueError(f"dim * nbits / 8 = {pb} bytes per token is not a multiple of 4")
    sizes = cluster_sizes(config)
    sizes = sizes[seed_rng(seed, 1).permutation(c)].astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)]).astype(np.int32)
    weights, cutoffs = codec_tables(config)
    cent, codes, docs = _fill(
        seed_key(seed), n_tokens=n, n_docs=config["n_docs"], n_centroids=c, dim=dim, pb=pb,
    )
    index = index_type(
        centroids=cent,
        packed_codes=codes,
        token_doc_ids=docs,
        cluster_offsets=jnp.asarray(offsets),
        cluster_sizes=jnp.asarray(sizes),
        bucket_weights=jnp.asarray(weights),
        bucket_cutoffs=jnp.asarray(cutoffs),
        dim=dim, nbits=nbits, cap=int(sizes.max()), n_docs=config["n_docs"], n_tokens=n,
    )
    return jax.block_until_ready(index), sizes


@jax.jit
def _perturb(centroids, cids, key, noise):
    q = centroids[cids] + noise * jax.random.normal(key, cids.shape + centroids.shape[1:])
    return q / jnp.linalg.norm(q, axis=-1, keepdims=True)


def make_queries(config: dict, index, sizes: np.ndarray, seed: int, n: int, stream: int, active: int):
    """``n`` queries of ``query_maxlen`` tokens, the first ``active`` of
    them live: (q f32[n, Q, D] on the host, qmask bool[n, Q]). ``stream``
    keeps pools of one seed apart."""
    qlen = config["query_maxlen"]
    rng = seed_rng(seed, 2 + stream)
    cids = rng.choice(len(sizes), size=(n, qlen), p=sizes / sizes.sum()).astype(np.int32)
    key = jax.random.fold_in(seed_key(seed), 1000 + stream)
    q = _perturb(index.centroids, jnp.asarray(cids), key, config["synthesis"]["query_noise"])
    if not 0 < active <= qlen:
        raise ValueError(f"active tokens {active} not in [1, query_maxlen={qlen}]")
    return np.asarray(q), np.broadcast_to(np.arange(qlen) < active, (n, qlen)).copy()
