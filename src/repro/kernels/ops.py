"""Public jit'd wrappers around the Pallas kernels.

Each op pads inputs to the kernel's tiling, runs interpret=True off-TPU
(interpret mode executes the kernel body in Python for correctness
validation), and slices the result back. Callers can
force the pure-jnp reference with ``use_kernel=False``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.decompress_score import selective_sum_kernel_call
from repro.kernels.embedding_bag import embedding_bag_kernel_call
from repro.fault import FAULTS as _FAULTS
from repro.kernels.fused_gather_score import (
    DEFAULT_BUFFERING,
    DEFAULT_RAGGED_TILE_C,
    DEFAULT_TILE_C,
    LANES,
    fused_gather_score_kernel_call,
    ragged_fused_gather_score_kernel_call,
    validate_tile_c,
)

__all__ = [
    "selective_sum",
    "fused_gather_selective_sum",
    "ragged_selective_sum",
    "ragged_fused_gather_selective_sum",
    "segmented_ragged_fused_gather_selective_sum",
    "resolve_tile_c",
    "resolve_tile_choice",
    "TileChoice",
    "embedding_bag",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _fault_kernel_call(op: str) -> None:
    """``engine.kernel_call`` injection point (``repro.fault``): fires at
    trace time — once per compilation, not per dispatch — modelling a
    kernel that fails to lower or launch on this backend. Disabled cost:
    one attribute check."""
    if _FAULTS.plan is not None:
        _FAULTS.plan.check("engine.kernel_call", op=op)


def _lane_tiled(pb: int) -> bool:
    """Whether packed rows of ``pb`` bytes tile the fused kernels'
    128-lane view of the codes (``fused_gather_score.lane_rows``)."""
    return pb <= LANES and LANES % pb == 0


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _check_packable_dim(dim: int, nbits: int, *, byte_wise: bool) -> None:
    """Byte-wise code consumers (Pallas kernels, the byte-LUT path) reshape
    packed rows as [PB, 8/nbits] and cannot skip the zero-padded trailing
    byte an odd ``dim`` produces; fail with direction instead of a reshape
    TypeError deep in the kernel."""
    per_byte = 8 // nbits
    if byte_wise and dim % per_byte:
        raise ValueError(
            f"dim={dim} does not fill whole {nbits}-bit packed bytes "
            f"({8 // nbits} dims/byte): the Pallas kernels and sum_impl="
            "'lut' index codes byte-wise and cannot skip the padded "
            "trailing byte — use executor='reference' with "
            "sum_impl='gather' (and gather='materialize') for this index"
        )


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """A resolved candidate-tile decision and where it came from.

    source: "config" (explicit ``cfg.tile_c`` override), "autotune" (a
    measured winner from ``kernels/autotune.py`` matched this index
    geometry on this backend), or "heuristic" (the analytic fallback).
    ``buffering`` is concrete ("double" | "single"): the tuned entry's
    schedule when the table supplied the tile, else the kernel default.
    """

    tile_c: int
    source: str
    buffering: str


def resolve_tile_choice(
    cap: int,
    tile_c: int | None = None,
    *,
    layout: str = "dense",
    n_tokens: int | None = None,
    nbits: int | None = None,
    dim: int | None = None,
    buffering: str = "auto",
    table: "autotune.AutotuneTable | None" = None,
) -> TileChoice:
    """Candidate tile row count for the fused kernels and worklists, with
    provenance — the single resolver every consumer funnels through.

    Precedence:
      1. An explicit ``tile_c`` wins unconditionally (source="config").
      2. With the full index geometry (``n_tokens``/``nbits``/``dim``),
         the autotune table is consulted: a backend-matched entry for this
         (geometry bucket, layout) supplies tile AND DMA schedule
         (source="autotune").
      3. The analytic heuristic: power-of-two >= 8 (the TPU sublane
         quantum) capped at the layout default — 128 for the dense grid
         (DMA efficiency; the masked tail is paid once per probe anyway)
         and 32 for ragged worklists (the per-cluster tail waste is
         < tile_c rows, so a tighter tile tracks skewed cluster sizes
         better) — and at the padded cap so tiny indexes don't over-pad
         (source="heuristic").

    ``buffering="auto"`` resolves to the tuned entry's schedule when the
    table supplied the tile, else ``DEFAULT_BUFFERING``; an explicit
    "double"/"single" always stands. The returned tile is validated
    against the double-buffered scratch budget when the geometry gives
    the packed byte width.
    """
    pb = dim * nbits // 8 if (dim is not None and nbits is not None) else None
    if tile_c is not None:
        chosen = TileChoice(
            tile_c,
            "config",
            DEFAULT_BUFFERING if buffering == "auto" else buffering,
        )
    else:
        tuned = None
        if n_tokens is not None and nbits is not None and dim is not None:
            tuned = (table or autotune.get_default_table()).lookup(
                "ragged" if layout == "ragged" else "dense",
                nbits=nbits, dim=dim, cap=cap, n_tokens=n_tokens,
            )
        if tuned is not None:
            chosen = TileChoice(
                tuned.tile_c,
                "autotune",
                tuned.buffering if buffering == "auto" else buffering,
            )
        else:
            default = (
                DEFAULT_RAGGED_TILE_C if layout == "ragged" else DEFAULT_TILE_C
            )
            tile = min(
                default, 1 << max(3, (cap - 1).bit_length() if cap > 1 else 3)
            )
            chosen = TileChoice(
                tile,
                "heuristic",
                DEFAULT_BUFFERING if buffering == "auto" else buffering,
            )
    validate_tile_c(
        chosen.tile_c, pb=pb, where=f"tile_c ({chosen.source})"
    )
    return chosen


def resolve_tile_c(
    cap: int,
    tile_c: int | None = None,
    *,
    layout: str = "dense",
    n_tokens: int | None = None,
    nbits: int | None = None,
    dim: int | None = None,
) -> int:
    """``resolve_tile_choice`` without the provenance — the tile alone.

    Callers that only know ``cap`` (no geometry kwargs) get the explicit
    override or the analytic heuristic, never an autotuned entry; plan
    resolution passes the geometry and persists the full choice into the
    config, so by execution time ``cfg.tile_c`` is concrete and this
    returns it unchanged.
    """
    return resolve_tile_choice(
        cap, tile_c, layout=layout, n_tokens=n_tokens, nbits=nbits, dim=dim
    ).tile_c


def selective_sum(
    packed: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    use_kernel: bool = True,
    tile_n: int | None = None,
    impl: str = "gather",
) -> jax.Array:
    """Dispatch implicit-decompression scoring to the Pallas kernel or ref.

    packed u8[Q, N, PB], v f32[Q, D, 2^b] -> f32[Q, N].
    impl (non-kernel path): "gather" (per-dim) | "lut" (byte-LUT, §Perf).
    """
    _check_packable_dim(dim, nbits, byte_wise=use_kernel or impl == "lut")
    if not use_kernel or nbits == 8:
        # b=8 means 256 select-accumulate unrolls; the gather-based ref is
        # the better lowering there.
        if impl == "lut":
            return ref.selective_sum_lut(packed, v, nbits=nbits, dim=dim)
        return ref.selective_sum(packed, v, nbits=nbits, dim=dim)
    q, n, pb = packed.shape
    if n == 0:
        # Degenerate candidate set: nothing to score, and the kernel's grid
        # (n // tile) would be empty anyway.
        return jnp.zeros((q, 0), jnp.float32)
    # Power-of-two tile >= 8 (the TPU sublane quantum), capped at 512 and at
    # the padded input length so tiny N doesn't over-pad.
    tile = tile_n or min(512, 1 << max(3, (n - 1).bit_length()))
    tile = max(8, min(tile, _round_up(n, 8)))
    n_pad = _round_up(n, tile)
    if n_pad != n:
        packed = jnp.pad(packed, ((0, 0), (0, n_pad - n), (0, 0)))
    _fault_kernel_call("selective_sum")
    out = selective_sum_kernel_call(
        packed, v, nbits=nbits, dim=dim, tile_n=tile, interpret=not on_tpu()
    )
    return out[:, :n]


def fused_gather_selective_sum(
    packed_codes: jax.Array,
    cluster_offsets: jax.Array,
    cluster_sizes: jax.Array,
    probe_cids: jax.Array,
    probe_scores: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    cap: int,
    n_tokens: int,
    use_kernel: bool = True,
    tile_c: int | None = None,
    impl: str = "fused",
    buffering: str = "auto",
    probe: str = "full",
) -> jax.Array:
    """Single-pass CSR probe + implicit decompression + scoring.

    packed_codes u8[N, PB] (resident index), cluster_offsets i32[C+1],
    cluster_sizes i32[C], probe_cids i32[Q, P], probe_scores f32[Q, P],
    v f32[Q, D, 2^b] -> cand_scores f32[Q, P, cap] (invalid slots zeroed).

    impl="fused" routes to the Pallas scalar-prefetch kernel (padding cap
    to the tile size, interpret=True off-TPU); any other value — or b=8,
    or an index too small to tile — falls back to the jnp reference, which
    gathers but is semantically identical.

    ``buffering`` picks the kernel's DMA schedule ("double" | "single",
    bit-identical; see fused_gather_score.py); "auto" takes the kernel
    default — plan resolution passes the concrete resolved choice.
    ``probe`` passes through the kernel's profiling carve-outs
    ("full" | "dma" | "compute"): non-"full" values time one half of the
    DMA/compute pipeline and return garbage scores, so they are rejected
    whenever this call would fall back to the jnp reference (which has
    no halves to carve).

    With ``use_kernel`` the dim must fill whole packed bytes — the Pallas
    kernel reshapes codes as [PB, per_byte] and cannot skip a padded
    trailing byte; the jnp reference (gather-based) handles any dim.
    """
    _check_packable_dim(dim, nbits, byte_wise=use_kernel and impl == "fused")
    if buffering == "auto":
        buffering = DEFAULT_BUFFERING
    starts = cluster_offsets[probe_cids].astype(jnp.int32)  # [Q, P]
    sizes = cluster_sizes[probe_cids].astype(jnp.int32)  # [Q, P]
    tile = resolve_tile_c(cap, tile_c)
    if (
        not use_kernel
        or impl != "fused"
        or nbits == 8  # 256 select-accumulate unrolls: ref lowers better
        or cap == 0
        or n_tokens < tile  # index smaller than one code tile
        or not _lane_tiled(packed_codes.shape[-1])
    ):
        if probe != "full":
            raise ValueError(
                f"probe={probe!r} requires the Pallas kernel path, but "
                "this call falls back to the jnp reference (use_kernel="
                f"{use_kernel}, impl={impl!r}, nbits={nbits}, cap={cap}, "
                f"n_tokens={n_tokens} vs tile {tile})"
            )
        return ref.fused_gather_score(
            packed_codes, starts, sizes, probe_scores, v,
            nbits=nbits, dim=dim, cap=cap,
        )
    cap_pad = _round_up(cap, tile)
    _fault_kernel_call("fused_gather_score")
    out = fused_gather_score_kernel_call(
        packed_codes, starts, sizes, probe_scores, v,
        nbits=nbits, dim=dim, n_tokens=n_tokens, cap_pad=cap_pad,
        tile_c=tile, buffering=buffering, probe=probe,
        interpret=not on_tpu(),
    )
    return out[:, :, :cap]


def ragged_selective_sum(
    packed: jax.Array,
    qtok: jax.Array,
    v: jax.Array,
    *,
    nbits: int,
    dim: int,
    impl: str = "gather",
) -> jax.Array:
    """Selective sum over a flat worklist-ordered candidate stream.

    packed u8[N_slots, PB], qtok i32[N_slots], v f32[Q, D, 2^b]
    -> f32[N_slots]. Slots from different query tokens are interleaved
    (worklist order), so there is no leading Q axis for the blocked Pallas
    selective-sum kernel to tile over — the ragged *materialize* path
    always scores with the jnp references (the kernel-accelerated ragged
    path is the fused one, ``ragged_fused_gather_selective_sum``).

    impl: "gather" (per-dim) | "lut" (byte-LUT), as in ``selective_sum``.
    """
    _check_packable_dim(dim, nbits, byte_wise=impl == "lut")
    if impl == "lut":
        return ref.ragged_selective_sum_lut(packed, qtok, v, nbits=nbits, dim=dim)
    return ref.ragged_selective_sum(packed, qtok, v, nbits=nbits, dim=dim)


def ragged_fused_gather_selective_sum(
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
    n_tokens: int,
    use_kernel: bool = True,
    buffering: str = "auto",
    probe: str = "full",
) -> jax.Array:
    """Single-pass worklist probe + implicit decompression + scoring.

    packed_codes u8[N, PB] (resident index), worklist arrays
    row0/nvalid/qtok i32[W] + pscore f32[W] (``core.worklist``),
    v f32[Q, D, 2^b] -> flat scores f32[W * tile_c] (invalid slots zeroed).

    Routes to the ragged Pallas scalar-prefetch kernel (interpret off-TPU);
    b=8 or an index smaller than one code tile falls back to the jnp
    reference, which gathers but is semantically identical. ``buffering``
    and the profiling ``probe`` carve-outs as in
    ``fused_gather_selective_sum`` (non-"full" probes need the kernel
    path and are rejected on the reference fallback).
    """
    _check_packable_dim(dim, nbits, byte_wise=use_kernel)
    if buffering == "auto":
        buffering = DEFAULT_BUFFERING
    validate_tile_c(tile_c, pb=packed_codes.shape[-1])
    if (
        not use_kernel
        or nbits == 8  # 256 select-accumulate unrolls: ref lowers better
        or n_tokens < tile_c  # index smaller than one code tile
        or row0.shape[0] == 0
        or not _lane_tiled(packed_codes.shape[-1])
    ):
        if probe != "full":
            raise ValueError(
                f"probe={probe!r} requires the Pallas kernel path, but "
                f"this call falls back to the jnp reference (use_kernel="
                f"{use_kernel}, nbits={nbits}, n_tokens={n_tokens} vs "
                f"tile {tile_c}, worklist len {row0.shape[0]})"
            )
        return ref.ragged_fused_gather_score(
            packed_codes, row0, nvalid, qtok, pscore, v,
            nbits=nbits, dim=dim, tile_c=tile_c,
        )
    _fault_kernel_call("ragged_fused_gather_score")
    return ragged_fused_gather_score_kernel_call(
        packed_codes, row0, nvalid, qtok, pscore, v,
        nbits=nbits, dim=dim, n_tokens=n_tokens, tile_c=tile_c,
        buffering=buffering, probe=probe, interpret=not on_tpu(),
    )


def segmented_ragged_fused_gather_selective_sum(
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
    use_kernel: bool = True,
    buffering: str = "auto",
) -> jax.Array:
    """Single-pass worklist probe + decompression + scoring across segments.

    ``packed_list`` holds each segment's resident ``u8[N_s, PB]`` codes
    (base first, deltas in append order); worklist arrays
    row0/nvalid/seg/qtok i32[W] + pscore f32[W] (``core.worklist`` with
    per-probe segment runs), v f32[Q, D, 2^b] -> flat scores
    f32[W * tile_c] (invalid slots zeroed).

    Kernel path: the ragged Pallas kernel is per-resident-array, so the
    worklist is replayed once per segment with other segments' entries
    masked to ``nvalid = 0`` — those tiles hit the kernel's ``pl.when``
    early-exit, so real work stays proportional to the true tile count and
    only grid-step overhead scales with ``n_segments``. Each slot is valid
    in exactly one segment and masked slots are exactly 0, so the
    per-segment outputs sum to the combined result. Kernel-vs-reference
    routing is PER SEGMENT: a delta smaller than one code tile scores via
    the jnp reference without de-optimizing the (possibly huge) base;
    b=8 or an empty worklist fall back entirely (same rules as the
    single-geometry dispatch).

    A single-segment call degenerates to
    ``ragged_fused_gather_selective_sum`` exactly.
    """
    _check_packable_dim(dim, nbits, byte_wise=use_kernel)
    if buffering == "auto":
        buffering = DEFAULT_BUFFERING
    if len(packed_list) == 1:
        return ragged_fused_gather_selective_sum(
            packed_list[0], row0, nvalid, qtok, pscore, v,
            nbits=nbits, dim=dim, tile_c=tile_c,
            n_tokens=packed_list[0].shape[0], use_kernel=use_kernel,
            buffering=buffering,
        )
    if (
        not use_kernel
        or nbits == 8  # 256 select-accumulate unrolls: ref lowers better
        or row0.shape[0] == 0
        or not _lane_tiled(packed_list[0].shape[-1])
    ):
        return ref.segmented_ragged_fused_gather_score(
            packed_list, row0, nvalid, seg, qtok, pscore, v,
            nbits=nbits, dim=dim, tile_c=tile_c,
        )
    _fault_kernel_call("segmented_ragged_fused_gather_score")
    out = jnp.zeros((row0.shape[0] * tile_c,), jnp.float32)
    pscore_f32 = pscore.astype(jnp.float32)
    for s, codes in enumerate(packed_list):
        if codes.shape[0] == 0:
            continue  # empty segment: owns no worklist entries
        nvalid_s = jnp.where(seg == s, nvalid, 0)
        if codes.shape[0] < tile_c:
            # Sub-tile segment (e.g. a tiny fresh delta): reference path
            # for THIS segment only; masked slots are exactly 0 either
            # way, so the sum stays the combined result.
            out = out + ref.ragged_fused_gather_score(
                codes, row0, nvalid_s, qtok, pscore_f32, v,
                nbits=nbits, dim=dim, tile_c=tile_c,
            )
            continue
        out = out + ragged_fused_gather_score_kernel_call(
            codes, row0, nvalid_s, qtok, pscore_f32, v,
            nbits=nbits, dim=dim, n_tokens=codes.shape[0], tile_c=tile_c,
            buffering=buffering, interpret=not on_tpu(),
        )
    return out


def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    segment_ids: jax.Array | None = None,
    *,
    num_segments: int | None = None,
    weights: jax.Array | None = None,
    use_kernel: bool = False,
    bag_indices: jax.Array | None = None,
    bag_weights: jax.Array | None = None,
) -> jax.Array:
    """EmbeddingBag(sum).

    Two call forms:
      - flat: (table, indices[N], segment_ids[N], num_segments) -> ref path
        (gather + segment_sum) — arbitrary vocab size, the production path.
      - padded: (table, bag_indices[S, L], bag_weights[S, L]) -> Pallas
        one-hot MXU kernel when ``use_kernel`` (vocab must be modest or a
        shard); falls back to a dense jnp computation of the same layout.
    """
    if bag_indices is not None:
        assert bag_weights is not None
        s, l = bag_indices.shape
        v_rows, d = table.shape
        if use_kernel:
            tile_s = min(8, s)
            blk_v = min(512, v_rows)
            s_pad = _round_up(s, tile_s)
            v_pad = _round_up(v_rows, blk_v)
            tbl = jnp.pad(table, ((0, v_pad - v_rows), (0, 0)))
            idx = jnp.pad(bag_indices, ((0, s_pad - s), (0, 0)))
            w = jnp.pad(bag_weights, ((0, s_pad - s), (0, 0)))
            out = embedding_bag_kernel_call(
                tbl, idx, w, tile_s=tile_s, blk_v=blk_v, interpret=not on_tpu()
            )
            return out[:s]
        rows = jnp.take(table, bag_indices.reshape(-1), axis=0).reshape(s, l, -1)
        return jnp.sum(rows * bag_weights[..., None], axis=1)

    assert segment_ids is not None and num_segments is not None
    return ref.embedding_bag(
        table, indices, segment_ids, num_segments=num_segments, weights=weights
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    tq: int = 128,
    tk: int = 128,
) -> jax.Array:
    """Flash-attention forward. q/k/v [B, S, H(kv), Dh] (layers.py layout);
    GQA handled by repeating KV heads. Pads S to the tile size."""
    from repro.kernels.flash_attention import flash_attention_kernel_call

    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    tq = min(tq, max(8, sq))
    tk = min(tk, max(8, skv))
    sq_p = _round_up(sq, tq)
    skv_p = _round_up(skv, tk)
    if skv_p != skv and not causal:
        # Padded key positions (> sq-1) are masked by causality; without
        # causality they would contribute — caller must pre-pad instead.
        raise ValueError("non-causal flash_attention requires Skv % tk == 0")
    qt = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0))), 1, 2)
    kt = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0))), 1, 2)
    vt = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0))), 1, 2)
    out = flash_attention_kernel_call(
        qt, kt, vt, causal=causal, window=window, tq=tq, tk=tk,
        interpret=not on_tpu(),
    )
    return jnp.moveaxis(out, 1, 2)[:, :sq]
