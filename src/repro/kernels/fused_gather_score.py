"""Pallas TPU kernel: fused gather + implicit decompression + scoring.

The two-step engine path materializes the full ``[Q, nprobe, cap, PB]``
uint8 candidate tensor in HBM (an XLA gather of ``packed_codes``) and then
reads it back in ``selective_sum`` — three passes over the candidate bytes
on a path the paper (§4.4) shows is memory-roofline bound. This kernel
collapses candidate generation's gather and the selective-sum into ONE pass
over the *resident* index:

  1. Scalar prefetch (``pltpu.PrefetchScalarGridSpec``): per-(query-token,
     probe) CSR cluster ``starts`` / ``sizes`` (from ``cluster_offsets``)
     and the centroid probe scores live in SMEM before the kernel body
     runs, MoE block-sparse style.
  2. The packed-code tile for grid step (q, p, j) — tokens
     ``[starts[q,p] + j*TILE_C, +TILE_C)`` of the resident array — is
     DMA'd straight from HBM into VMEM. No pre-gathered copy exists in
     HBM at any point. With ``buffering="double"`` (the default) the
     DMA is an explicit ``pltpu.make_async_copy`` into a
     ``[2, window, 128]`` scratch with manual slot rotation: tile j+1's
     copy is issued before tile j's unpack+accumulate runs, so the DMA
     engine and the VPU/MXU overlap instead of serializing.
     ``buffering="single"`` keeps the BlockSpec-driven fetch (the default
     Pallas pipeline, element-indexed rows) — same bits, no manual overlap.
  3. In VMEM the b-bit codes are unpacked and scored against the
     per-query-token v-table by ``decompress_score.score_rows`` — the
     formulation every kernel of this package shares.
  4. The centroid probe score ``S_cq`` is added and slots beyond the true
     cluster size are masked to 0, so the output is the final
     ``[Q, nprobe, cap]`` candidate-score tensor in one write (one
     ``[1, TILE_C]`` row per grid step, eight steps per ``[8, TILE_C]``
     output block; see ``decompress_score.store_row``).

Fetch windows: the kernels read the codes as ``u8[rows, 128]``
(``lane_rows``; two tokens per row at PB = 64), because a DMA out of the
tiled HBM array moves whole 128-lane rows and starts on a ROW_ALIGN-row
boundary. A tile's fetch is the aligned window of ``window_rows`` rows
that holds its tokens, clamped to the last window so it never reads out
of bounds; the wanted tokens sit ``shift`` positions into it, and an
exact placement matmul moves them to slots ``0..TILE_C-1``
(``_tile_scores``). Valid slots (``c < size``) always land inside the
window because ``start + size <= n_tokens`` for every cluster — the
overhang is exactly the masked tail. The window arithmetic is computed
identically under both bufferings (the double-buffered kernel inside its
copy descriptor, the single-buffered one inside the BlockSpec index map),
so the two paths are bit-exact.

Double-buffer slot rotation: grid steps are numbered by their linear step
index; step s computes on ``scratch[s % 2]`` and issues the DMA for step
s+1 into ``scratch[(s+1) % 2]`` before waiting on its own slot. At most
two copies are in flight, always on distinct slots, and a slot's semaphore
is waited exactly once per started copy. On the ragged grid the
``pl.when`` early-exit is preserved: a padding tile (``nvalid == 0``)
neither starts nor waits a DMA — its slot's start/wait guards read the
same prefetched ``nvalid``, so semaphore accounting stays balanced and
real work (DMA *and* compute) stays proportional to the true tile count.

VMEM budget per grid step: two ``[window, 128]`` uint8 code windows
(TILE_C=128, b=4, D=128 -> 18 KiB), the ``[per_byte * 2^b, 128]`` f32
v-table (16 KiB at b=4), and an ``[8, TILE_C]`` f32 output block — far
under the ~16 MiB VMEM. TILE_C trades DMA efficiency against the
masked-tail waste for small clusters; ``ops.resolve_tile_c`` consults the
profile-driven autotune table (``kernels/autotune.py``) when one matches
the index geometry and otherwise picks ``min(128, next_pow2(cap))``
analytically. ``validate_tile_c`` rejects tiles the kernels cannot run
with a directed error.

The ``probe`` knob carves the kernel into measurable halves for the
autotune sweep (``benchmarks/bench_autotune.py``): "full" is the product
path; "dma" runs the tile DMAs but replaces unpack+accumulate with a
trivial per-slot sink; "compute" (double-buffered only) runs
unpack+accumulate on whatever is resident in scratch without issuing any
copies. total/dma/compute timings give the DMA-vs-compute split and the
achieved overlap fraction.

Off-TPU the kernel runs under ``interpret=True`` (pure-Python body over an
XLA grid loop) — bit-identical semantics, used by the parity tests; DMAs
execute synchronously there, so interpret-mode overlap fractions are ~0
by construction and only TPU runs measure real overlap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decompress_score import (
    OUT_ROWS,
    score_rows,
    slot_major_table,
    store_row,
)

__all__ = [
    "fused_gather_score_kernel_call",
    "ragged_fused_gather_score_kernel_call",
    "validate_tile_c",
    "DEFAULT_TILE_C",
    "DEFAULT_RAGGED_TILE_C",
    "DEFAULT_BUFFERING",
    "BUFFERINGS",
    "KERNEL_PROBES",
    "DB_SCRATCH_BYTES_MAX",
]

DEFAULT_TILE_C = 128
# Ragged worklists favour smaller tiles: the per-cluster padding waste is
# ceil(size/tile)*tile - size (< tile_c rows), so a tighter tile tracks
# skewed cluster sizes better at the cost of more grid steps. 32 keeps the
# sublane dimension well above the 8-row quantum while roughly quartering
# the tail waste vs the dense default.
DEFAULT_RAGGED_TILE_C = 32

# Candidate-tile DMA scheduling: "double" = explicit [2, window, 128] VMEM
# scratch with manual slot rotation (tile j+1's copy overlaps tile j's
# unpack+accumulate); "single" = the BlockSpec-driven fetch.
BUFFERINGS = ("double", "single")
DEFAULT_BUFFERING = "double"

# Autotune-sweep measurement carve-outs; "full" is the product path.
KERNEL_PROBES = ("full", "dma", "compute")

# Ceiling for the double-buffered code scratch (2 * tile_c * PB u8 bytes).
# Deliberately far below the ~16 MiB/core VMEM: the scratch shares VMEM
# with the v-table block, the output block, and the compiler's own
# temporaries, and a tile this large has long since stopped helping DMA
# efficiency.
DB_SCRATCH_BYTES_MAX = 4 << 20

# The kernels read the codes as u8[rows, LANES] (``lane_rows``): a DMA out
# of the tiled HBM array moves whole 128-lane rows and starts on a
# ROW_ALIGN row boundary.
LANES = 128
ROW_ALIGN = 8

# Worklist tiles one ragged pallas_call scalar-prefetches: its four i32/f32
# arrays then take 512 KiB of the 1 MiB SMEM. Longer worklists run as
# several calls over consecutive chunks.
MAX_WORKLIST_TILES = 32768


def validate_tile_c(tile_c: int, *, pb: int | None = None, where: str = "tile_c") -> int:
    """Directed rejection of candidate-tile sizes the kernels can't run.

    Every consumer of a tile size — the dense/ragged kernel calls, the
    worklist builder, ``ops.resolve_tile_c`` — funnels through this check,
    so a bad ``cfg.tile_c`` fails with direction instead of a shape error
    deep in a kernel. With ``pb`` (packed bytes per row) known, also
    rejects tiles whose ``[2, tile_c, PB]`` double-buffered VMEM scratch
    would exceed ``DB_SCRATCH_BYTES_MAX``.
    """
    if not isinstance(tile_c, (int,)) or isinstance(tile_c, bool):
        raise ValueError(f"{where}={tile_c!r} must be an int")
    if tile_c < 8 or tile_c % 8:
        raise ValueError(
            f"{where}={tile_c} must be a positive multiple of 8 (the TPU "
            "sublane quantum); the fused gather-score kernels tile "
            "candidate rows in sublane-aligned blocks"
        )
    if pb is not None and 2 * tile_c * pb > DB_SCRATCH_BYTES_MAX:
        raise ValueError(
            f"{where}={tile_c}: the double-buffered code scratch "
            f"[2, {tile_c}, {pb}] u8 needs {2 * tile_c * pb} bytes of VMEM, "
            f"over the {DB_SCRATCH_BYTES_MAX}-byte budget — lower tile_c "
            "(or nbits/dim) so two in-flight code tiles fit"
        )
    return tile_c


def _check_buffering(buffering: str) -> None:
    if buffering not in BUFFERINGS:
        raise ValueError(
            f"buffering={buffering!r} is not a valid DMA schedule; expected "
            f"one of {BUFFERINGS}"
        )


def _check_probe(probe: str, buffering: str) -> None:
    if probe not in KERNEL_PROBES:
        raise ValueError(
            f"probe={probe!r} is not a valid kernel carve-out; expected one "
            f"of {KERNEL_PROBES}"
        )
    if probe == "compute" and buffering != "double":
        raise ValueError(
            "probe='compute' isolates the unpack+accumulate half by "
            "skipping the tile DMAs, which only the double-buffered kernel "
            "can do (the single-buffered BlockSpec pipeline always "
            "fetches); use buffering='double'"
        )


def tokens_per_row(pb: int) -> int:
    """Tokens of ``pb`` packed bytes that one 128-lane kernel row holds."""
    if pb > LANES or LANES % pb:
        raise ValueError(
            f"packed rows of {pb} bytes do not tile the {LANES}-lane kernel "
            "view of the codes; ops.py routes such an index to the jnp "
            "reference"
        )
    return LANES // pb


def window_rows(tile_c: int, tpr: int) -> int:
    """Kernel rows one tile fetch moves: the tile's rows plus one
    alignment quantum, so an aligned window always covers the tile."""
    rows = -(-tile_c // tpr) + ROW_ALIGN
    return -(-rows // ROW_ALIGN) * ROW_ALIGN


def lane_rows(packed_codes: jax.Array, tile_c: int) -> jax.Array:
    """u8[N, PB] -> the kernels' u8[rows, 128] view of the codes.

    Row r holds tokens ``r * tpr .. r * tpr + tpr - 1`` side by side
    (``tpr = 128 // PB``): a DMA out of the tiled HBM array must move
    whole 128-lane rows and start on a ROW_ALIGN row boundary. The tail is
    zero-padded to a ROW_ALIGN multiple of rows and to at least one
    window; padded tokens sit past ``n_tokens`` and are always masked.
    """
    n, pb = packed_codes.shape
    tpr = tokens_per_row(pb)
    rows = max(-(-n // (tpr * ROW_ALIGN)) * ROW_ALIGN, window_rows(tile_c, tpr))
    if rows * tpr != n:
        packed_codes = jnp.pad(packed_codes, ((0, rows * tpr - n), (0, 0)))
    return packed_codes.reshape(rows, LANES)


def _fetch_start(row0, tpr: int, n_rows: int, win: int):
    """ROW_ALIGN-aligned first kernel row of the ``win``-row window that
    holds tokens ``[row0, row0 + tile_c)`` clipped to the array: round the
    token's row down to the quantum, clamped to the last window (``n_rows``
    and ``win`` are both quantum multiples, so the clamp stays aligned)."""
    start = jnp.clip(row0 // tpr, 0, n_rows - win)
    return pl.multiple_of(start - start % ROW_ALIGN, ROW_ALIGN)


def _tile_scores(tile, w, row0, nlimit, extra, *, nbits, tpr, n_rows, tile_c,
                 probe):
    """Shared compute half of every fused kernel: score, re-align, mask.

    tile u8[win, 128] is the window fetched from kernel row
    ``_fetch_start(row0)``; the wanted token ``row0 + c`` sits at window
    position ``c + shift``. ``score_rows`` scores every token of the
    window as ``[tpr, win]``; one exact 0/1 placement matmul per in-row
    token position moves token ``r * tpr + h`` to slot
    ``r * tpr + h - shift``. Slot c is valid when ``c < nlimit``; valid
    slots get ``+ extra`` (the centroid probe score), invalid ones
    exactly 0. Returns f32[1, TILE_C]. One definition keeps the single-
    and double-buffered kernels bit-identical by construction.
    """
    win = tile.shape[0]
    shift = row0 - _fetch_start(row0, tpr, n_rows, win) * tpr
    x = tile.astype(jnp.int32)
    c = jax.lax.broadcasted_iota(jnp.int32, (1, tile_c), 1)
    if probe == "dma":
        # DMA-only carve-out: the fetch ran; sink one code into the row so
        # the store cannot be elided, skip unpack+accumulate.
        acc = jnp.broadcast_to(x[0:1, 0:1].astype(jnp.float32), c.shape)
    else:
        per_token = score_rows(x, w, nbits=nbits, tokens_per_row=tpr)
        r = jax.lax.broadcasted_iota(jnp.int32, (win, tile_c), 0)
        slot = jax.lax.broadcasted_iota(jnp.int32, (win, tile_c), 1) + shift
        acc = extra
        for h in range(tpr):
            place = (r * tpr + h == slot).astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                per_token[h:h + 1, :], place, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
    return jnp.where(c < nlimit, acc, 0.0)


def _out_rows(steps: int) -> int:
    return ((steps + OUT_ROWS - 1) // OUT_ROWS) * OUT_ROWS




def _prepare(packed_codes, v, *, nbits, dim, n_tokens, tile_c, buffering,
             probe):
    """Shared argument checks + operand views of both kernel calls:
    returns (codes u8[rows, 128], v-table f32[Q, per_byte * 2^b, 128],
    tpr, window rows)."""
    n, pb = packed_codes.shape
    qm = v.shape[0]
    nb = 1 << nbits
    _check_buffering(buffering)
    _check_probe(probe, buffering)
    validate_tile_c(tile_c, pb=pb)
    if n != n_tokens:
        raise ValueError(
            f"static n_tokens={n_tokens} does not match packed_codes rows {n}"
        )
    if n < tile_c:
        raise ValueError(
            f"index has {n} token rows, below one tile_c={tile_c} tile; "
            "ops.py should have routed this to the jnp reference"
        )
    if v.shape != (qm, dim, nb):
        raise ValueError(f"v shape {v.shape} != {(qm, dim, nb)}")
    tpr = tokens_per_row(pb)
    codes = lane_rows(packed_codes, tile_c)
    w = slot_major_table(v, nbits, tokens_per_row=tpr)
    return codes, w, tpr, window_rows(tile_c, tpr)


# ---------------------------------------------------------------------------
# Dense grid: (Q, nprobe, cap_pad / tile_c)
# ---------------------------------------------------------------------------


def _fused_kernel(
    starts_ref,  # SMEM i32[Q, P]   cluster token starts (prefetched)
    sizes_ref,  # SMEM i32[Q, P]   cluster sizes (prefetched)
    pscore_ref,  # SMEM f32[Q, P]   centroid probe scores (prefetched)
    codes_ref,  # VMEM u8[WIN, 128]  window of kernel rows (element-indexed)
    w_ref,  # VMEM f32[1, per_byte * 2^b, 128]  this query token's v-table
    out_ref,  # VMEM f32[OUT_ROWS, TILE_C]
    **kw,
):
    q, p, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = (q * pl.num_programs(1) + p) * pl.num_programs(2) + j
    tile_c = kw["tile_c"]
    row = _tile_scores(
        codes_ref[...], w_ref[0], starts_ref[q, p] + j * tile_c,
        sizes_ref[q, p] - j * tile_c, pscore_ref[q, p], **kw,
    )
    store_row(out_ref, step, row)


def _fused_kernel_db(
    starts_ref,  # SMEM i32[Q, P]   cluster token starts (prefetched)
    sizes_ref,  # SMEM i32[Q, P]   cluster sizes (prefetched)
    pscore_ref,  # SMEM f32[Q, P]   centroid probe scores (prefetched)
    codes_hbm,  # ANY  u8[rows, 128]  the resident index (never gathered)
    w_ref,  # VMEM f32[1, per_byte * 2^b, 128]  this query token's v-table
    out_ref,  # VMEM f32[OUT_ROWS, TILE_C]
    scratch_ref,  # VMEM u8[2, WIN, 128]  double-buffered code windows
    sem_ref,  # DMA semaphores [2]
    **kw,
):
    q, p, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_p, n_j = pl.num_programs(1), pl.num_programs(2)
    # Linear step index drives the slot rotation: step s computes on
    # scratch[s % 2] while the DMA for step s+1 fills scratch[(s+1) % 2].
    step = (q * n_p + p) * n_j + j
    total = pl.num_programs(0) * n_p * n_j
    tile_c, probe = kw["tile_c"], kw["probe"]
    win = scratch_ref.shape[1]

    def tile_dma(slot, qq, pp, jj):
        # Same aligned window as the single-buffered index map, so the
        # two bufferings are bit-exact.
        start = _fetch_start(
            starts_ref[qq, pp] + jj * tile_c, kw["tpr"], kw["n_rows"], win
        )
        return pltpu.make_async_copy(
            codes_hbm.at[pl.ds(start, win)],
            scratch_ref.at[slot],
            sem_ref.at[slot],
        )

    if probe != "compute":

        @pl.when(step == 0)
        def _():
            # Warm-up: the first tile has nobody to prefetch it.
            tile_dma(0, q, p, 0).start()

        @pl.when(step + 1 < total)
        def _():
            # Issue tile s+1's copy before waiting on our own — this is
            # the overlap. Decode the next grid step from its linear index
            # (j fastest, then p, then q — the TPU grid iteration order).
            nxt = step + 1
            j2 = nxt % n_j
            p2 = (nxt // n_j) % n_p
            q2 = nxt // (n_j * n_p)
            tile_dma(nxt % 2, q2, p2, j2).start()

        tile_dma(step % 2, q, p, j).wait()

    row = _tile_scores(
        scratch_ref[step % 2], w_ref[0], starts_ref[q, p] + j * tile_c,
        sizes_ref[q, p] - j * tile_c, pscore_ref[q, p], **kw,
    )
    store_row(out_ref, step, row)


@functools.partial(
    jax.jit,
    static_argnames=(
        "nbits", "dim", "n_tokens", "cap_pad", "tile_c", "buffering",
        "probe", "interpret",
    ),
)
def fused_gather_score_kernel_call(
    packed_codes: jax.Array,
    starts: jax.Array,
    sizes: jax.Array,
    probe_scores: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    n_tokens: int,
    cap_pad: int,
    tile_c: int = DEFAULT_TILE_C,
    buffering: str = DEFAULT_BUFFERING,
    probe: str = "full",
    interpret: bool = False,
) -> jax.Array:
    """Fused CSR probe + selective sum.

    packed_codes u8[N, PB] (the resident index — never gathered),
    starts/sizes i32[Q, P], probe_scores f32[Q, P], v f32[Q, D, 2^b]
    -> scores f32[Q, P, cap_pad] with invalid slots (c >= sizes) zeroed.

    ``cap_pad`` must be a tile_c multiple and n_tokens >= tile_c (ops.py
    enforces both; it falls back to the jnp reference otherwise).
    ``buffering`` picks the DMA schedule ("double": explicit
    [2, window, 128] scratch, manual slot rotation; "single": the
    BlockSpec pipeline) — bit-identical outputs. ``probe`` carves the
    kernel for the autotune sweep ("full" | "dma" | "compute").
    """
    codes, w, tpr, win = _prepare(
        packed_codes, v, nbits=nbits, dim=dim, n_tokens=n_tokens,
        tile_c=tile_c, buffering=buffering, probe=probe,
    )
    qm, p = starts.shape
    if cap_pad % tile_c:
        raise ValueError(f"cap_pad={cap_pad} not a multiple of tile_c={tile_c}")
    n_rows = codes.shape[0]
    n_j = cap_pad // tile_c
    steps = qm * p * n_j
    grid = (qm, p, n_j)  # dense: every probe pays cap_pad
    w_spec = pl.BlockSpec((1,) + w.shape[1:], lambda q, pp, j, *_: (q, 0, 0))
    out_spec = pl.BlockSpec(
        (OUT_ROWS, tile_c),
        lambda q, pp, j, *_: (((q * p + pp) * n_j + j) // OUT_ROWS, 0),
    )
    if buffering == "double":
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                # The resident codes stay in HBM; the kernel body issues
                # explicit double-buffered copies of its windows.
                pl.BlockSpec(memory_space=pl.ANY),
                w_spec,
            ],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((2, win, LANES), jnp.uint8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        kernel = _fused_kernel_db
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (pl.Element(win), pl.Element(LANES)),
                    lambda q, pp, j, starts, sizes, ps: (
                        _fetch_start(starts[q, pp] + j * tile_c, tpr,
                                     n_rows, win),
                        0,
                    ),
                ),
                w_spec,
            ],
            out_specs=out_spec,
        )
        kernel = _fused_kernel
    out = pl.pallas_call(
        functools.partial(
            kernel, nbits=nbits, tpr=tpr, n_rows=n_rows, tile_c=tile_c,
            probe=probe,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((_out_rows(steps), tile_c), jnp.float32),
        interpret=interpret,
        name="warp_fused_gather_score_dense",
    )(starts, sizes, probe_scores.astype(jnp.float32), codes, w)
    return out[:steps].reshape(qm, p, cap_pad)


# ---------------------------------------------------------------------------
# Ragged grid: 1-D over worklist tiles
# ---------------------------------------------------------------------------


def _ragged_kernel(
    row0_ref,  # SMEM i32[W]  tile token starts (prefetched)
    nvalid_ref,  # SMEM i32[W]  valid slots per tile (0 => padding tile)
    qtok_ref,  # SMEM i32[W]  owning query token per tile (prefetched)
    pscore_ref,  # SMEM f32[W]  centroid probe score per tile (prefetched)
    codes_ref,  # VMEM u8[WIN, 128]  this tile's window (element-indexed)
    w_ref,  # VMEM f32[1, per_byte * 2^b, 128]  the owning token's v-table
    out_ref,  # VMEM f32[OUT_ROWS, TILE_C]
    **kw,
):
    w = pl.program_id(0)
    nvalid = nvalid_ref[w]

    # Early-exit: padding tiles past the true worklist length (and probes
    # whose remaining rows ran out) skip the 2^b select-accumulate entirely.
    @pl.when(nvalid == 0)
    def _():
        store_row(out_ref, w, jnp.zeros((1, kw["tile_c"]), jnp.float32))

    @pl.when(nvalid > 0)
    def _():
        row = _tile_scores(
            codes_ref[...], w_ref[0], row0_ref[w], nvalid, pscore_ref[w],
            **kw,
        )
        store_row(out_ref, w, row)


def _ragged_kernel_db(
    row0_ref,  # SMEM i32[W]  tile token starts (prefetched)
    nvalid_ref,  # SMEM i32[W]  valid slots per tile (0 => padding tile)
    qtok_ref,  # SMEM i32[W]  owning query token per tile (prefetched)
    pscore_ref,  # SMEM f32[W]  centroid probe score per tile (prefetched)
    codes_hbm,  # ANY  u8[rows, 128]  the resident index (never gathered)
    w_ref,  # VMEM f32[1, per_byte * 2^b, 128]  the owning token's v-table
    out_ref,  # VMEM f32[OUT_ROWS, TILE_C]
    scratch_ref,  # VMEM u8[2, WIN, 128]  double-buffered code windows
    sem_ref,  # DMA semaphores [2]
    **kw,
):
    w = pl.program_id(0)
    nw = pl.num_programs(0)
    nvalid = nvalid_ref[w]
    win = scratch_ref.shape[1]

    def tile_dma(slot, ww):
        start = _fetch_start(row0_ref[ww], kw["tpr"], kw["n_rows"], win)
        return pltpu.make_async_copy(
            codes_hbm.at[pl.ds(start, win)],
            scratch_ref.at[slot],
            sem_ref.at[slot],
        )

    if kw["probe"] != "compute":
        # pl.when early-exit composes with the rotation: a padding tile
        # (nvalid == 0) neither starts nor waits a DMA. Each step's start
        # and wait are guarded by the SAME prefetched nvalid, so every
        # started copy is waited exactly once and slots never collide —
        # steps s and s+1 use opposite slots by construction.
        @pl.when((w == 0) & (nvalid_ref[0] > 0))
        def _():
            tile_dma(0, 0).start()

        # Clamp the lookahead read so the last step stays in bounds; the
        # w + 1 < nw conjunct makes the clamped value irrelevant.
        nv_next = nvalid_ref[jnp.minimum(w + 1, nw - 1)]

        @pl.when((w + 1 < nw) & (nv_next > 0))
        def _():
            tile_dma((w + 1) % 2, w + 1).start()

    @pl.when(nvalid == 0)
    def _():
        store_row(out_ref, w, jnp.zeros((1, kw["tile_c"]), jnp.float32))

    @pl.when(nvalid > 0)
    def _():
        if kw["probe"] != "compute":
            tile_dma(w % 2, w).wait()
        row = _tile_scores(
            scratch_ref[w % 2], w_ref[0], row0_ref[w], nvalid, pscore_ref[w],
            **kw,
        )
        store_row(out_ref, w, row)


@functools.partial(
    jax.jit,
    static_argnames=(
        "nbits", "dim", "n_tokens", "tile_c", "buffering", "probe",
        "interpret",
    ),
)
def ragged_fused_gather_score_kernel_call(
    packed_codes: jax.Array,
    row0: jax.Array,
    nvalid: jax.Array,
    qtok: jax.Array,
    pscore: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    n_tokens: int,
    tile_c: int = DEFAULT_RAGGED_TILE_C,
    buffering: str = DEFAULT_BUFFERING,
    probe: str = "full",
    interpret: bool = False,
) -> jax.Array:
    """Worklist-driven fused CSR probe + selective sum (ragged layout).

    Where ``fused_gather_score_kernel_call`` runs a dense
    ``(Q, nprobe, cap_pad / tile_c)`` grid — every probe slot pays for the
    global max cluster size — this variant runs a 1-D grid over the tiles
    of a prefix-summed tile worklist (``core.worklist``): one grid step per
    *real* candidate tile, plus statically-bounded padding tiles that
    early-exit via ``pl.when``. Per step, the prefetched ``row0`` drives a
    DMA of the tile's code window straight from the resident index —
    explicit double-buffered copies under ``buffering="double"`` (padding
    tiles skip the DMA too), the default BlockSpec pipeline under
    "single" — and ``qtok`` picks the owning query token's v-table block.

    packed_codes u8[N, PB], row0/nvalid/qtok i32[W], pscore f32[W],
    v f32[Q, D, 2^b] -> flat scores f32[W * tile_c] with invalid slots
    (c >= nvalid, incl. all slots of padding tiles) zeroed.
    """
    codes, wt, tpr, win = _prepare(
        packed_codes, v, nbits=nbits, dim=dim, n_tokens=n_tokens,
        tile_c=tile_c, buffering=buffering, probe=probe,
    )
    n_rows = codes.shape[0]
    w_spec = pl.BlockSpec(
        (1,) + wt.shape[1:], lambda i, row0, nvalid, qtok, ps: (qtok[i], 0, 0)
    )
    out_spec = pl.BlockSpec((OUT_ROWS, tile_c), lambda i, *_: (i // OUT_ROWS, 0))
    if buffering == "double":
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), w_spec]
        scratch = [
            pltpu.VMEM((2, win, LANES), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        kernel = _ragged_kernel_db
    else:
        in_specs = [
            pl.BlockSpec(
                (pl.Element(win), pl.Element(LANES)),
                lambda i, row0, nvalid, qtok, ps: (
                    _fetch_start(row0[i], tpr, n_rows, win), 0
                ),
            ),
            w_spec,
        ]
        scratch = []
        kernel = _ragged_kernel
    kernel = functools.partial(
        kernel, nbits=nbits, tpr=tpr, n_rows=n_rows, tile_c=tile_c,
        probe=probe,
    )
    pscore = pscore.astype(jnp.float32)
    outs = []
    for lo in range(0, row0.shape[0], MAX_WORKLIST_TILES):
        chunk = [a[lo:lo + MAX_WORKLIST_TILES] for a in (row0, nvalid, qtok, pscore)]
        w = chunk[0].shape[0]
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(w,),
                in_specs=in_specs,
                out_specs=out_spec,
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((_out_rows(w), tile_c), jnp.float32),
            interpret=interpret,
            name="warp_fused_gather_score_ragged",
        )(*chunk, codes, wt)
        outs.append(out[:w].reshape(-1))
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]
