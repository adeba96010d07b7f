"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contract: each kernel's tests sweep shapes/dtypes and
assert allclose against the functions here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import unpack_codes

__all__ = [
    "selective_sum",
    "selective_sum_lut",
    "ragged_selective_sum",
    "ragged_selective_sum_lut",
    "embedding_bag",
    "fused_gather_score",
    "ragged_fused_gather_score",
    "segmented_ragged_gather_codes",
    "segmented_ragged_fused_gather_score",
]


@functools.partial(jax.jit, static_argnames=("nbits", "dim", "d_chunk"))
def selective_sum(
    packed: jax.Array, v: jax.Array, *, nbits: int, dim: int, d_chunk: int = 32
) -> jax.Array:
    """Implicit-decompression scoring (paper Eq. 5), reference semantics.

    packed: u8[Q, N, D*nbits/8]  packed residual codes of candidate tokens.
    v:      f32[Q, D, 2^b]       per-query-token lookup table v = q ⊗ ω.
    returns f32[Q, N] with out[q, n] = sum_d v[q, d, codes[q, n, d]].

    The per-dim gather accumulates over D in chunks of ``d_chunk`` (a scan)
    so the [Q, N, D] gathered-values intermediate never materializes —
    peak extra memory is [Q, N, d_chunk] (§Perf hillclimb, warp-xtr cell).
    (The centroid term S_cq of Eq. 5 is added by the caller.)
    """
    q, n, _ = packed.shape
    codes = unpack_codes(packed, nbits, dim).astype(jnp.int32)  # [Q, N, D]
    if dim % d_chunk:
        d_chunk = dim
    n_chunks = dim // d_chunk
    # [C, Q, N, Dc] / [C, Q, Dc, B]
    codes_c = jnp.moveaxis(codes.reshape(q, n, n_chunks, d_chunk), 2, 0)
    v_c = jnp.moveaxis(v.reshape(q, n_chunks, d_chunk, -1), 1, 0)

    def step(acc, inp):
        cc, vc = inp
        g = jnp.take_along_axis(vc[:, None, :, :], cc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(g, axis=-1), None

    out, _ = jax.lax.scan(step, jnp.zeros((q, n), jnp.float32), (codes_c, v_c))
    return out


def _byte_lut(v: jax.Array, nbits: int) -> jax.Array:
    """Fold the per-dimension v-table into a per-byte LUT:
    lut[q, j, byte] = sum over the 8/nbits dims packed into byte j of
    v[q, dim, digit]. Shared by the dense and ragged LUT paths."""
    q = v.shape[0]
    per_byte = 8 // nbits
    nb = 1 << nbits
    pb = v.shape[1] // per_byte  # packed bytes per code row
    byte_vals = jnp.arange(256, dtype=jnp.int32)
    # v grouped by byte: [Q, PB, per_byte, 2^b]
    vg = v.reshape(q, pb, per_byte, nb)
    lut = jnp.zeros((q, pb, 256), jnp.float32)
    for slot in range(per_byte):
        digits = (byte_vals >> (slot * nbits)) & (nb - 1)  # [256]
        lut = lut + vg[:, :, slot, digits]
    return lut


@functools.partial(jax.jit, static_argnames=("nbits", "dim"))
def selective_sum_lut(
    packed: jax.Array, v: jax.Array, *, nbits: int, dim: int
) -> jax.Array:
    """Byte-LUT selective sum (beyond-paper, FAISS-PQ-style):

        score[q, n] = sum_j lut[q, j, packed[q, n, j]]

    where lut[q, j, byte] pre-folds the 8/nbits dimensions packed into
    byte j: one 256-entry gather per BYTE instead of one 2^b-entry gather
    per DIMENSION — 2x (b=4) / 4x (b=2) fewer gathers and no unpacking.
    Semantically identical to selective_sum (parity-tested).
    """
    lut = _byte_lut(v, nbits)
    idx = packed.astype(jnp.int32)  # [Q, N, PB]
    gathered = jnp.take_along_axis(lut[:, None, :, :], idx[..., None], axis=-1)[..., 0]
    return jnp.sum(gathered, axis=-1)


@functools.partial(jax.jit, static_argnames=("nbits", "dim", "d_chunk"))
def ragged_selective_sum(
    packed: jax.Array,
    qtok: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    d_chunk: int = 32,
) -> jax.Array:
    """Selective sum over a FLAT candidate stream with per-slot query tokens.

    packed u8[N, PB] (one packed code row per worklist slot),
    qtok i32[N] owning query token of each slot, v f32[Q, D, 2^b]
    -> f32[N] with out[n] = sum_d v[qtok[n], d, codes[n, d]].

    The ragged-layout analogue of ``selective_sum``: the flat stream mixes
    query tokens (worklist order), so the v-row is picked per slot by one
    3-operand gather instead of aligning on a leading Q axis. Chunked over
    D with the same chunk size and summation order as ``selective_sum`` so
    a slot's score is identical bit-for-bit across layouts.
    """
    n = packed.shape[0]
    codes = unpack_codes(packed[None], nbits, dim)[0].astype(jnp.int32)  # [N, D]
    if dim % d_chunk:
        d_chunk = dim
    n_chunks = dim // d_chunk
    q = v.shape[0]
    codes_c = jnp.moveaxis(codes.reshape(n, n_chunks, d_chunk), 1, 0)  # [C, N, Dc]
    v_c = jnp.moveaxis(v.reshape(q, n_chunks, d_chunk, -1), 1, 0)  # [C, Q, Dc, B]
    d_idx = jnp.arange(d_chunk, dtype=jnp.int32)

    def step(acc, inp):
        cc, vc = inp  # [N, Dc] / [Q, Dc, B]
        g = vc[qtok[:, None], d_idx[None, :], cc]  # [N, Dc] gather
        return acc + jnp.sum(g, axis=-1), None

    out, _ = jax.lax.scan(step, jnp.zeros((n,), jnp.float32), (codes_c, v_c))
    return out


@functools.partial(jax.jit, static_argnames=("nbits", "dim"))
def ragged_selective_sum_lut(
    packed: jax.Array, qtok: jax.Array, v: jax.Array, *, nbits: int, dim: int
) -> jax.Array:
    """Byte-LUT variant of ``ragged_selective_sum`` (see selective_sum_lut):
    out[n] = sum_j lut[qtok[n], j, packed[n, j]]."""
    n, pb = packed.shape
    lut = _byte_lut(v, nbits)
    j_idx = jnp.arange(pb, dtype=jnp.int32)
    gathered = lut[qtok[:, None], j_idx[None, :], packed.astype(jnp.int32)]
    return jnp.sum(gathered, axis=-1)


@functools.partial(jax.jit, static_argnames=("nbits", "dim", "cap"))
def fused_gather_score(
    packed_codes: jax.Array,
    starts: jax.Array,
    sizes: jax.Array,
    probe_scores: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    cap: int,
) -> jax.Array:
    """Semantics oracle for the fused gather–decompress–score kernel.

    packed_codes u8[N, PB], starts/sizes i32[Q, P], probe_scores f32[Q, P],
    v f32[Q, D, 2^b] -> f32[Q, P, cap] where slot (q, p, c) is
    ``probe_scores[q, p] + sum_d v[q, d, code_d]`` of token
    ``starts[q, p] + c`` when ``c < sizes[q, p]`` and exactly 0 otherwise.

    This reference *does* gather (it is the contract, not the fast path);
    the Pallas kernel must match it bit-for-bit on valid slots and on the
    zero masking.
    """
    qm, p = starts.shape
    n = packed_codes.shape[0]
    pos = starts[..., None] + jnp.arange(cap, dtype=jnp.int32)  # [Q, P, cap]
    valid = jnp.arange(cap, dtype=jnp.int32) < sizes[..., None]
    # Clamp floor 0: n == 0 must not produce a -1 wraparound gather.
    pos = jnp.clip(pos, 0, max(0, n - 1))
    gathered = packed_codes[pos]  # [Q, P, cap, PB]
    scores = selective_sum(
        gathered.reshape(qm, p * cap, -1), v, nbits=nbits, dim=dim
    ).reshape(qm, p, cap)
    return jnp.where(valid, scores + probe_scores[..., None], 0.0)


@functools.partial(jax.jit, static_argnames=("nbits", "dim", "tile_c"))
def ragged_fused_gather_score(
    packed_codes: jax.Array,
    row0: jax.Array,
    nvalid: jax.Array,
    qtok: jax.Array,
    pscore: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    tile_c: int,
) -> jax.Array:
    """Semantics oracle for the ragged worklist kernel.

    packed_codes u8[N, PB] (resident index), worklist arrays
    row0/nvalid/qtok i32[W] + pscore f32[W] (``core.worklist``),
    v f32[Q, D, 2^b] -> flat f32[W * tile_c] where slot (w, c) is
    ``pscore[w] + sum_d v[qtok[w], d, code_d]`` of token ``row0[w] + c``
    when ``c < nvalid[w]`` and exactly 0 otherwise.

    Like ``fused_gather_score`` this reference *does* gather; the Pallas
    kernel must match it on valid slots and on the zero masking. Slot
    expansion is shared with the engine's materialize path
    (``worklist_slot_positions``) so the clamp/validity semantics of a
    worklist tile have exactly one definition.
    """
    from repro.core.worklist import TileWorklist, worklist_slot_positions

    wl = TileWorklist(row0=row0, nvalid=nvalid, qtok=qtok, pscore=pscore)
    pos, valid = worklist_slot_positions(
        wl, tile_c=tile_c, n_tokens=packed_codes.shape[0]
    )
    gathered = packed_codes[pos]  # [W * tile_c, PB]
    qtok_slot = jnp.repeat(qtok, tile_c)
    scores = ragged_selective_sum(gathered, qtok_slot, v, nbits=nbits, dim=dim)
    scores = scores + jnp.repeat(pscore, tile_c)
    return jnp.where(valid, scores, 0.0)


@functools.partial(jax.jit, static_argnames=("tile_c",))
def segmented_ragged_gather_codes(
    packed_list: tuple[jax.Array, ...],
    row0: jax.Array,
    nvalid: jax.Array,
    seg: jax.Array,
    *,
    tile_c: int,
) -> tuple[jax.Array, jax.Array]:
    """Gather a segmented worklist's code rows into one flat stream.

    ``packed_list`` holds each segment's resident ``u8[N_s, PB]`` codes;
    worklist entries carry *segment-local* ``row0`` plus the owning ``seg``
    id (``core.worklist``). Per segment the slot positions are clamped
    into that segment's row range (floor 0, same rule as
    ``worklist_slot_positions``) and the right segment's rows are selected
    per slot — returns (codes u8[W * tile_c, PB], valid bool[W * tile_c]).
    Shared by the segmented materialize path and the fused oracle so slot
    semantics have exactly one definition.
    """
    w = row0.shape[0]
    pb = packed_list[0].shape[1]
    lane = jnp.arange(tile_c, dtype=jnp.int32)
    pos = row0[:, None] + lane[None, :]  # [W, tile_c] segment-local
    valid = lane[None, :] < nvalid[:, None]
    gathered = jnp.zeros((w, tile_c, pb), jnp.uint8)
    for s, codes in enumerate(packed_list):
        n_s = codes.shape[0]
        if n_s == 0:
            continue  # empty segment holds no worklist entries
        pos_s = jnp.clip(pos, 0, n_s - 1)
        own = (seg == s)[:, None, None]
        gathered = jnp.where(own, codes[pos_s], gathered)
    return gathered.reshape(w * tile_c, pb), valid.reshape(-1)


@functools.partial(jax.jit, static_argnames=("nbits", "dim", "tile_c"))
def segmented_ragged_fused_gather_score(
    packed_list: tuple[jax.Array, ...],
    row0: jax.Array,
    nvalid: jax.Array,
    seg: jax.Array,
    qtok: jax.Array,
    pscore: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    tile_c: int,
) -> jax.Array:
    """Semantics oracle for segmented ragged worklist scoring.

    The segmented analogue of ``ragged_fused_gather_score``: one flat
    worklist spans the base plus delta segments, each entry's ``seg``
    naming the segment whose (segment-local) ``row0`` rows it scores.
    Returns flat f32[W * tile_c] where slot (w, c) is
    ``pscore[w] + sum_d v[qtok[w], d, code_d]`` of row ``row0[w] + c`` of
    segment ``seg[w]`` when ``c < nvalid[w]`` and exactly 0 otherwise.
    Scoring goes through ``ragged_selective_sum`` (same d-chunk order as
    the dense path) so a slot's score is bit-identical across layouts and
    segmentations.
    """
    gathered, valid = segmented_ragged_gather_codes(
        packed_list, row0, nvalid, seg, tile_c=tile_c
    )
    qtok_slot = jnp.repeat(qtok, tile_c)
    scores = ragged_selective_sum(gathered, qtok_slot, v, nbits=nbits, dim=dim)
    scores = scores + jnp.repeat(pscore, tile_c)
    return jnp.where(valid, scores, 0.0)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    segment_ids: jax.Array,
    *,
    num_segments: int,
    weights: jax.Array | None = None,
) -> jax.Array:
    """EmbeddingBag(sum): out[s] = sum_{i: seg[i]==s} w[i] * table[idx[i]].

    table:       f32[V, D]
    indices:     i32[N]  rows to gather.
    segment_ids: i32[N]  bag id per index (need not be sorted).
    returns      f32[num_segments, D]
    """
    rows = jnp.take(table, indices, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    return jax.ops.segment_sum(rows, segment_ids, num_segments=num_segments)
