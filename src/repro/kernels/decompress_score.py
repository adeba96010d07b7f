"""Pallas TPU kernel: implicit decompression + selective sum (paper §4.4).

The paper's C++ kernel walks packed residual bytes, unpacks nibbles with
bitwise ops, and accumulates ``v[d, code_d]`` per candidate token. A literal
port would serialize the TPU's vector unit on per-element gathers, so the
TPU-native formulation is:

  1. widen the packed bytes to int32 and unpack each b-bit slot of a byte
     with shift/AND — fully vectorized on the VPU. Slot ``s`` of byte ``j``
     is dimension ``j * per_byte + s``, so slot ``s`` over all bytes is the
     strided dimension set ``s::per_byte``. Instead of interleaving the
     slots back into dimension order (a lane shuffle Mosaic cannot lower),
     the v-table is permuted once, outside the kernel, into the matching
     *slot-major* order (``slot_major_table``);
  2. replace the per-dimension *gather* ``v[d, code_d]`` with a
     select chain over the 2^b buckets:
         vals[n, j] = sum_s v[j * per_byte + s, code_s[n, j]]
     Since 2^b is 4 or 16, this is a short static unroll of dense VPU
     ops. Each selected value is exact, so only the final row sum rounds;
  3. sum each token's lanes of ``vals`` on the MXU as ``sel @ vals^T``
     (``sel`` holds one 0/1 row per token of a row), which lands the
     per-candidate scores lane-major (a ``[1, TILE]`` row per token slot)
     and runs at ``Precision.HIGHEST`` (f32 contraction) — the chip's
     default one-pass bf16 would round every selected value to 8 mantissa
     bits.

Output layout: each grid step produces one ``[1, TILE]`` score row. Mosaic
needs an output block whose trailing pair divides by (8, 128) or equals the
array's, so the kernels write into a ``[rows, TILE]`` array through
``[8, TILE]`` blocks: eight consecutive grid steps share one block, each
writing its own row (``store_row``). The block is written back when the
grid moves on to the next eight steps.

Tiling: grid (Q, N / TILE_N). Per step the kernel holds one
``[TILE_N, PB]`` uint8 code tile, the ``[per_byte * 2^b, PB]`` f32 v-table
of one query token, and an ``[8, TILE_N]`` f32 output block in VMEM.

This kernel consumes a *pre-gathered* candidate tensor: the engine's
two-step path first materializes ``[Q, nprobe, cap, PB]`` codes in HBM
(XLA gather) and this kernel reads them back — i.e. every candidate byte
crosses HBM three times (index read at gather, gather write, kernel read).
``fused_gather_score.py`` is the single-pass evolution: it scalar-prefetches
the CSR probe metadata and pulls code tiles straight from the resident
index, eliminating the gathered copy entirely (engine strategy
``WarpSearchConfig(gather="fused")``). This two-step kernel remains the
baseline and the drop-in for callers that already hold gathered codes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "selective_sum_kernel_call",
    "slot_major_table",
    "score_rows",
    "store_row",
    "DEFAULT_TILE_N",
    "OUT_ROWS",
]

DEFAULT_TILE_N = 512

# Score rows per output block: the f32 sublane quantum.
OUT_ROWS = 8


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def slot_major_table(
    v: jax.Array, nbits: int, *, tokens_per_row: int = 1
) -> jax.Array:
    """v f32[Q, D, 2^b] -> w f32[Q, per_byte * 2^b, PB * tokens_per_row]
    with ``w[q, s * 2^b + b, t * PB + j] = v[q, j * per_byte + s, b]``.

    Row ``s * 2^b + b`` is bucket b's value for the dimensions that slot s
    of every packed byte holds, so ``score_rows`` never re-interleaves the
    unpacked slots; it repeats once per token a kernel row holds.
    """
    q, dim, nb = v.shape
    per_byte = 8 // nbits
    pb = dim // per_byte
    w = v.astype(jnp.float32).reshape(q, pb, per_byte, nb)
    w = w.transpose(0, 2, 3, 1).reshape(q, per_byte * nb, pb)
    return jnp.tile(w, (1, 1, tokens_per_row))


def score_rows(
    packed: jax.Array, w: jax.Array, *, nbits: int, tokens_per_row: int = 1
) -> jax.Array:
    """packed int[R, L], w f32[per_byte * 2^b, L] -> scores f32[tpr, R].

    Each row holds ``tpr = tokens_per_row`` tokens of ``L / tpr`` packed
    bytes side by side; ``scores[h, r] = sum_d v[d, code_d]`` of token h of
    row r. One definition serves every kernel in this package.
    """
    nb = 1 << nbits
    per_byte = 8 // nbits
    x = packed.astype(jnp.int32)
    vals = None
    for slot in range(per_byte):
        codes = (x >> (slot * nbits)) & (nb - 1)
        base = slot * nb
        part = jnp.broadcast_to(w[base:base + 1, :], x.shape)
        for bucket in range(1, nb):
            part = jnp.where(
                codes == bucket, w[base + bucket:base + bucket + 1, :], part
            )
        vals = part if vals is None else vals + part
    lanes = x.shape[1]
    owner = jax.lax.broadcasted_iota(jnp.int32, (tokens_per_row, lanes), 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (tokens_per_row, lanes), 0)
    sel = (owner // (lanes // tokens_per_row) == token).astype(jnp.float32)
    return jax.lax.dot_general(
        sel, vals, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def store_row(out_ref, step, row) -> None:
    """Write grid step ``step``'s ``[1, T]`` row into its ``[OUT_ROWS, T]``
    output block (the block holds steps ``step // OUT_ROWS * OUT_ROWS``
    onward)."""
    out_ref[pl.ds(step % OUT_ROWS, 1), :] = row


def _selective_sum_kernel(packed_ref, w_ref, out_ref, *, nbits: int):
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    store_row(out_ref, step, score_rows(packed_ref[0], w_ref[0], nbits=nbits))


@functools.partial(
    jax.jit, static_argnames=("nbits", "dim", "tile_n", "interpret")
)
def selective_sum_kernel_call(
    packed: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """packed u8[Q, N, PB], v f32[Q, D, 2^b] -> scores f32[Q, N].

    N must be a multiple of tile_n (ops.py pads).
    """
    q, n, pb = packed.shape
    nb = 1 << nbits
    if n % tile_n:
        raise ValueError(f"N={n} not a multiple of tile_n={tile_n}")
    if v.shape != (q, dim, nb):
        raise ValueError(f"v shape {v.shape} != {(q, dim, nb)}")
    w = slot_major_table(v, nbits)
    n_t = n // tile_n
    rows = q * n_t
    out = pl.pallas_call(
        functools.partial(_selective_sum_kernel, nbits=nbits),
        grid=(q, n_t),
        in_specs=[
            pl.BlockSpec((1, tile_n, pb), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1,) + w.shape[1:], lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (OUT_ROWS, tile_n), lambda i, j: ((i * n_t + j) // OUT_ROWS, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (_round_up(rows, OUT_ROWS), tile_n), jnp.float32
        ),
        interpret=interpret,
        name="warp_decompress_score",
    )(packed, w)
    return out[:rows].reshape(q, n)
