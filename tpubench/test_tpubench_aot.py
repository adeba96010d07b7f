"""The reference compiled for a described TPU v5e, at a million tokens:
it reads the code array where it lies, with no copy of it and little
scratch, so it runs beside an index that fills most of the chip.

On the chip the codes ``u8[N, 64]`` lie token-minor (``{0,1}``). A
reference that gathers rows of them makes the compiler copy the whole
array into a row-major layout that pads each row to 128 bytes: twice the
index again, which one LoTTE Pooled shard (165,010,000 tokens) cannot
hold. The topology is described inside a module fixture, never at
import; the persistent compilation cache is off around these compiles.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from tpubench import reference

N, C, CAP, N_DOCS, Q, D, PB, K = 1_000_000, 8192, 250, 5000, 32, 128, 64, 100
SCRATCH_LIMIT = 16 * 2**20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, lead: tuple):
    """The program that reads the codes: the whole reference on one shard
    (no shard axis), or steps 3-4 on one shard of several (a shard axis
    of 1, as the program's stacked index holds it on each device)."""

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    corpus = (
        sds(lead + (N, PB), jnp.uint8), sds(lead + (N,), jnp.int32), sds(lead + (C + 1,), jnp.int32),
        sds(lead + (C,), jnp.int32), sds(lead + (16,), jnp.float32),
    )
    q, qmask, served = sds((Q, D), jnp.float32), sds((Q,), jnp.bool_), sds((K,), jnp.int32)
    static = dict(nprobe=32, k=K, n_docs=N_DOCS, cap=CAP, nbits=4, dtype=jnp.float32)
    if not lead:
        return reference.reference_query.lower(
            sds((C, D), jnp.float32), *corpus, q, qmask, served, k_impute=64, t_prime=1000, **static
        ).compile()
    top = sds((Q, 64), jnp.float32), sds((Q, 64), jnp.int32)
    return reference.shard_answer.lower(
        *corpus, q, qmask, *top, sds((Q,), jnp.float32), served, sds((), jnp.int32), **static
    ).compile()


@pytest.mark.parametrize("lead", [(), (1,)], ids=["one_shard", "shard_of_several"])
def test_the_reference_reads_the_codes_where_they_lie(one_chip, lead):
    compiled = _compile(one_chip, lead)
    shape = ",".join(map(str, lead + (N, PB)))
    # Every instruction that yields the whole code array: only the
    # parameter itself, and views of it that move no byte.
    whole = [
        line.strip() for line in compiled.as_text().splitlines()
        if re.search(rf"= u8\[{shape}\]", line)
    ]
    made = [line for line in whole if not re.search(r"\b(parameter|bitcast|get-tuple-element)\(", line)]
    assert whole and not made, made[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < SCRATCH_LIMIT
