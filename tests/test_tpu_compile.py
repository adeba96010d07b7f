"""Ahead-of-time compiles for a described TPU v5e at LoTTE-Lifestyle widths.

The retrieval kernels and the single-chip retrieve step are compiled by the
TPU compiler for a chip that is described, not attached: what Mosaic or
XLA:TPU would refuse (block shapes, unaligned DMAs, unsupported vector
ops, SMEM or HBM overflow) fails here, on the CPU. Nothing runs, so these
say nothing about results or speed; the interpret-mode parity tests and
``chip_smoke.py`` cover results.

The topology is described inside a module-scoped fixture (never at import
or collection time): only the process that runs this file's tests loads
the TPU compiler library. The persistent compilation cache is off around
these compiles — entries written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.warp_family import WARP_SHAPES, WarpArchConfig
from repro.core import engine
from repro.core.types import WarpIndex, WarpSearchConfig
from repro.kernels import ops
from repro.kernels.decompress_score import selective_sum_kernel_call
from repro.kernels.fused_gather_score import (
    MAX_WORKLIST_TILES,
    fused_gather_score_kernel_call,
    ragged_fused_gather_score_kernel_call,
)

SHAPE = WARP_SHAPES["search_lifestyle"]
ARCH = WarpArchConfig()
N, C, CAP = SHAPE.n_tokens, SHAPE.n_centroids, SHAPE.cap
D, NBITS, Q, P = ARCH.dim, ARCH.nbits, ARCH.query_maxlen, ARCH.nprobe
PB, NB = D * NBITS // 8, 1 << NBITS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The process sees the CPU, so ops.py would pick interpret mode;
    steer it to the compiled kernels for the described chip."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def test_selective_sum_kernel_compiles(one_chip):
    text = _compiled_text(
        lambda p, v: selective_sum_kernel_call(p, v, nbits=NBITS, dim=D),
        _sds(one_chip, (Q, P * CAP, PB), jnp.uint8),
        _sds(one_chip, (Q, D, NB), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert "%warp_decompress_score" in text  # the trace's stable op name


@pytest.mark.parametrize("buffering", ["double", "single"])
def test_fused_dense_kernel_compiles(one_chip, buffering):
    text = _compiled_text(
        lambda c, s, z, ps, v: fused_gather_score_kernel_call(
            c, s, z, ps, v, nbits=NBITS, dim=D, n_tokens=N, cap_pad=CAP,
            tile_c=128, buffering=buffering,
        ),
        _sds(one_chip, (N, PB), jnp.uint8),
        _sds(one_chip, (Q, P), jnp.int32),
        _sds(one_chip, (Q, P), jnp.int32),
        _sds(one_chip, (Q, P), jnp.float32),
        _sds(one_chip, (Q, D, NB), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert "%warp_fused_gather_score_dense" in text


@pytest.mark.parametrize(
    "buffering,tile_c", [("double", 32), ("single", 32), ("double", 16)]
)
def test_ragged_kernel_compiles(one_chip, buffering, tile_c):
    """At the Lifestyle worklist bound; tile_c=16 doubles the worklist past
    one call's SMEM budget, so it runs as several chunked calls."""
    w = Q * P * CAP // tile_c
    text = _compiled_text(
        lambda c, r, nv, qt, ps, v: ragged_fused_gather_score_kernel_call(
            c, r, nv, qt, ps, v, nbits=NBITS, dim=D, n_tokens=N,
            tile_c=tile_c, buffering=buffering,
        ),
        _sds(one_chip, (N, PB), jnp.uint8),
        *[_sds(one_chip, (w,), jnp.int32)] * 3,
        _sds(one_chip, (w,), jnp.float32),
        _sds(one_chip, (Q, D, NB), jnp.float32),
    )
    assert text.count("tpu_custom_call") >= -(-w // MAX_WORKLIST_TILES)
    assert "%warp_fused_gather_score_ragged" in text


def _lifestyle_index(sharding) -> WarpIndex:
    return WarpIndex(
        centroids=_sds(sharding, (C, D), jnp.float32),
        packed_codes=_sds(sharding, (N, PB), jnp.uint8),
        token_doc_ids=_sds(sharding, (N,), jnp.int32),
        cluster_offsets=_sds(sharding, (C + 1,), jnp.int32),
        cluster_sizes=_sds(sharding, (C,), jnp.int32),
        bucket_weights=_sds(sharding, (NB,), jnp.float32),
        bucket_cutoffs=_sds(sharding, (NB - 1,), jnp.float32),
        dim=D, nbits=NBITS, cap=CAP, n_docs=SHAPE.n_docs, n_tokens=N,
    )


def test_retrieve_batch_step_compiles(one_chip, tpu_dispatch):
    """The program the server dispatches: a batch of one query through a
    resolved kernel-executor plan (``engine._search_many``), fused gather.
    The slowest test of the file: the reduction's 1M-entry sort and
    top-k take the TPU compiler tens of seconds."""
    cfg = WarpSearchConfig(
        nprobe=P, k=ARCH.k, gather="fused", executor="kernel",
        reduce_impl="segment", t_prime=int(N**0.5), k_impute=ARCH.k_impute,
        tile_c=128, tile_source="config", buffering="double",
    )
    text = _compiled_text(
        lambda idx, q, m: engine._search_many(idx, q, m, cfg),
        _lifestyle_index(one_chip),
        _sds(one_chip, (1, Q, D), jnp.float32),
        _sds(one_chip, (1, Q), jnp.bool_),
    )
    assert "tpu_custom_call" in text
