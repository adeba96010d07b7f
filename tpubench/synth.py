"""A WARP index and a query pool made from ``--seed``, on the devices.

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

A configuration with ``shards`` is a document-sharded corpus: each shard
is such an index of ``n_tokens / shards`` tokens and ``n_centroids /
shards`` centroids over its own range of documents, made on its own
device (``make_shards``), and the queries are drawn over the centroids of
all shards.
"""

from __future__ import annotations

import dataclasses
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


@functools.partial(
    jax.jit, static_argnames=("n_tokens", "n_docs", "n_centroids", "dim", "pb", "lead")
)
def _fill(key, *, n_tokens, n_docs, n_centroids, dim, pb, lead):
    """Centroids, codes and doc ids in one program, each shaped
    ``lead + `` its own shape; codes and doc ids are filled in chunks so
    no temporary is larger than one chunk."""
    k_cent, k_codes, k_docs = jax.random.split(key, 3)
    cent = jax.random.normal(k_cent, (n_centroids, dim), jnp.float32)
    cent = cent / jnp.linalg.norm(cent, axis=1, keepdims=True)
    rows = min(CHUNK_ROWS, n_tokens)
    n_chunks = -(-n_tokens // rows)
    at = (0,) * len(lead)

    def body(i, bufs):
        codes, docs = bufs
        # The last chunk overlaps the one before it rather than run past
        # the end; its rows are simply drawn again.
        start = jnp.minimum(i * rows, n_tokens - rows)
        bits = jax.random.bits(jax.random.fold_in(k_codes, i), (rows, pb // 4), jnp.uint32)
        chunk = jax.lax.bitcast_convert_type(bits, jnp.uint8).reshape(lead + (rows, pb))
        d = jax.random.randint(jax.random.fold_in(k_docs, i), (rows,), 0, n_docs, jnp.int32)
        return (
            jax.lax.dynamic_update_slice(codes, chunk, at + (start, 0)),
            jax.lax.dynamic_update_slice(docs, d.reshape(lead + (rows,)), at + (start,)),
        )

    codes = jnp.zeros(lead + (n_tokens, pb), jnp.uint8)
    docs = jnp.zeros(lead + (n_tokens,), jnp.int32)
    codes, docs = jax.lax.fori_loop(0, n_chunks, body, (codes, docs))
    return cent.reshape(lead + cent.shape), codes, docs


@dataclasses.dataclass(frozen=True)
class Shard:
    """One document shard as the benchmark made it, on its own device.
    In a sharded corpus each device array has a leading axis of 1: it is
    the very buffer the program's stacked index holds on that device."""

    device: object
    centroids: jax.Array  # f32[C, D]
    codes: jax.Array  # u8[N, D * nbits / 8]
    doc_ids: jax.Array  # i32[N], local: 0 .. n_docs - 1
    offsets: jax.Array  # i32[C + 1]
    sizes: jax.Array  # i32[C]
    weights: jax.Array  # f32[2^nbits]
    cutoffs: jax.Array  # f32[2^nbits - 1]
    host_sizes: np.ndarray  # the cluster sizes, on the host
    doc_start: int  # global id of the shard's first document
    n_docs: int

    @property
    def cap(self) -> int:
        return int(self.host_sizes.max())


def make_shards(config: dict, seed: int, devices) -> list[Shard]:
    """The corpus of one run: shard ``s`` made on ``devices[s]`` from the
    seed, with ``n_tokens / shards`` tokens, ``n_centroids / shards``
    centroids, the one multiset of cluster sizes every shard shares (so
    every shard compiles the same program), dealt in an order and filled
    from a key of its own, and doc ids local to a contiguous range of
    ``n_docs // shards`` or one more documents. One shard is the whole
    index, made as the one-chip benchmark always made it."""
    s_count = config.get("shards", 1)
    if len(devices) != s_count:
        raise ValueError(f"{s_count} shards need as many devices, got {len(devices)}")
    n, c = config["n_tokens"] // s_count, config["n_centroids"] // s_count
    dim, nbits = config["dim"], config["nbits"]
    pb = dim * nbits // 8
    if pb % 4:
        raise ValueError(f"dim * nbits / 8 = {pb} bytes per token is not a multiple of 4")
    multiset = cluster_sizes(dict(config, n_tokens=n, n_centroids=c))
    weights, cutoffs = codec_tables(config)
    lead = () if s_count == 1 else (1,)
    shards = []
    for s, dev in enumerate(devices):
        lo, hi = s * config["n_docs"] // s_count, (s + 1) * config["n_docs"] // s_count
        rng = seed_rng(seed, 1) if s == 0 else np.random.default_rng([1, seed, s])
        sizes = multiset[rng.permutation(c)].astype(np.int32)
        offsets = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)]).astype(np.int32)
        key = seed_key(seed) if s == 0 else jax.random.fold_in(seed_key(seed), s)
        cent, codes, docs = _fill(
            jax.device_put(key, dev), n_tokens=n, n_docs=hi - lo, n_centroids=c, dim=dim, pb=pb, lead=lead,
        )
        put = lambda x: jax.device_put(x.reshape(lead + x.shape), dev)  # noqa: E731
        shards.append(Shard(
            device=dev, centroids=cent, codes=codes, doc_ids=docs, offsets=put(offsets),
            sizes=put(sizes), weights=put(weights), cutoffs=put(cutoffs), host_sizes=sizes,
            doc_start=lo, n_docs=hi - lo,
        ))
    for sh in shards:
        jax.block_until_ready((sh.centroids, sh.codes, sh.doc_ids))
    return shards


def warp_index(config: dict, shard: Shard, index_type):
    """A one-shard corpus as ``index_type`` (the program's ``WarpIndex``)."""
    return index_type(
        centroids=shard.centroids,
        packed_codes=shard.codes,
        token_doc_ids=shard.doc_ids,
        cluster_offsets=shard.offsets,
        cluster_sizes=shard.sizes,
        bucket_weights=shard.weights,
        bucket_cutoffs=shard.cutoffs,
        dim=config["dim"], nbits=config["nbits"], cap=shard.cap, n_docs=config["n_docs"],
        n_tokens=config["n_tokens"],
    )


def sharded_index(config: dict, shards: list[Shard], index_type, mesh):
    """A sharded corpus as ``index_type`` (the program's
    ``ShardedWarpIndex``) over the ``("data",)`` axis of ``mesh``: each
    stacked array is assembled from the shards' own buffers, so no device
    holds a second copy of its shard."""
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec("data"))

    def stack(arrays):
        return jax.make_array_from_single_device_arrays(
            (len(arrays),) + arrays[0].shape[1:], sharding, arrays
        )

    doc_start = [jax.device_put(np.asarray([sh.doc_start], np.int32), sh.device) for sh in shards]
    return index_type(
        centroids=stack([sh.centroids for sh in shards]),
        packed_codes=stack([sh.codes for sh in shards]),
        token_doc_ids=stack([sh.doc_ids for sh in shards]),
        cluster_offsets=stack([sh.offsets for sh in shards]),
        cluster_sizes=stack([sh.sizes for sh in shards]),
        bucket_weights=stack([sh.weights for sh in shards]),
        doc_start=stack(doc_start),
        dim=config["dim"], nbits=config["nbits"], cap=shards[0].cap, n_docs=config["n_docs"],
        n_tokens_padded=config["n_tokens"] // len(shards), n_tokens_total=config["n_tokens"],
        local_docs=max(sh.n_docs for sh in shards),
    )


@jax.jit
def _perturb(centroids, cids, key, noise):
    centroids = centroids.reshape(centroids.shape[-2:])
    q = centroids[cids] + noise * jax.random.normal(key, cids.shape + centroids.shape[1:])
    return q / jnp.linalg.norm(q, axis=-1, keepdims=True)


def make_queries(config: dict, shards: list[Shard], seed: int, n: int, stream: int, active: int):
    """``n`` queries of ``query_maxlen`` tokens, the first ``active`` of
    them live, over the centroids of all shards in proportion to their
    cluster sizes: (q f32[n, Q, D] on the host, qmask bool[n, Q]).
    ``stream`` keeps pools of one seed apart."""
    qlen = config["query_maxlen"]
    if not 0 < active <= qlen:
        raise ValueError(f"active tokens {active} not in [1, query_maxlen={qlen}]")
    rng = seed_rng(seed, 2 + stream)
    sizes = np.concatenate([sh.host_sizes for sh in shards])
    cids = rng.choice(len(sizes), size=(n, qlen), p=sizes / sizes.sum()).astype(np.int32)
    key = jax.random.fold_in(seed_key(seed), 1000 + stream)
    noise = config["synthesis"]["query_noise"]
    # Each shard perturbs the centroids drawn from it where they lie, with
    # the same noise; the host keeps each token from its own shard.
    c = len(shards[0].host_sizes)
    q = None
    for s, sh in enumerate(shards):
        mine = cids // c == s
        put = functools.partial(jax.device_put, device=sh.device)
        q_s = np.asarray(_perturb(sh.centroids, put(np.where(mine, cids % c, 0)), put(key), noise))
        q = q_s if q is None else np.where(mine[..., None], q_s, q)
    return q, np.broadcast_to(np.arange(qlen) < active, (n, qlen)).copy()
