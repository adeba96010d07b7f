"""Paper Fig. 1 / Fig. 9 / Tables 2-3 (latency columns): end-to-end latency
and per-stage breakdown of WARP vs the XTR-reference and PLAID-style
baselines, across three dataset tiers.

Stages (paper Fig. 4): query encoding | candidate generation (WARP_SELECT)
| decompression (implicit, selective-sum) | scoring (two-stage reduction).

The decompression and scoring rows carry ``derived`` occupancy fields —
``real_slots`` (true candidates in the probed clusters), ``padded_slots``
(what the layout pays for), and ``sort_n`` (the reduction's lax.sort
width) — so the ragged layout's win (compute ∝ real candidates instead of
``nprobe × cap``) is visible in the BENCH_latency.json trajectory, not
just in wall-clock. The ``*_ragged_adaptive`` rows run the same stages
under the query-adaptive bucket (the smallest ladder rung fitting the
measured query's probe set) next to the static worst-case bound, and the
per-tier plan snapshot records the bucket ladder and the chosen bucket —
on the Zipf-routed tier the adaptive sort-N sits strictly below the
static ragged one.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_autotune import dense_point, ragged_point, sweep_probe_set
from benchmarks.common import (
    PLANS,
    candidate_traffic_bytes,
    emit,
    get_setup,
    make_query_stream,
    time_fn,
)
from repro.core import Retriever, WarpSearchConfig, plaid_style_search, xtr_reference
from repro.core.engine import (
    gather_candidates,
    gather_doc_ids,
    ragged_flat_candidates,
    resolve_config,
)
from repro.core import worklist
from repro.core.reduction import two_stage_reduce
from repro.core.warpselect import warp_select
from repro.kernels import ops
from repro.models.encoder import EncoderConfig, TokenEncoder

_ENC = EncoderConfig(n_layers=4, d_model=256, n_heads=4, d_ff=512, vocab=32128)


def _stage_fns(index, config):
    config = resolve_config(index, config)
    config_ragged = resolve_config(
        index, dataclasses.replace(config, layout="ragged")
    )

    @jax.jit
    def stage_select(q, qmask):
        return warp_select(
            q, index.centroids, index.cluster_sizes,
            nprobe=config.nprobe, t_prime=config.t_prime,
            k_impute=config.k_impute, qmask=qmask,
        )

    @jax.jit
    def stage_gather(probe_cids):
        # The two-step path's "DMA": the XLA gather that materializes the
        # [Q, P, cap, PB] candidate tensor. Timed alone so the two-step
        # decompression row can report its data-movement / compute split.
        return gather_candidates(index, probe_cids)

    @jax.jit
    def stage_decompress(q, probe_scores, probe_cids):
        packed, doc_ids, valid = gather_candidates(index, probe_cids)
        qm, p, cap = packed.shape[0], config.nprobe, index.cap
        v = q[:, :, None] * index.bucket_weights[None, None, :]
        scores = ops.selective_sum(
            packed.reshape(qm, p * cap, -1), v,
            nbits=index.nbits, dim=index.dim, use_kernel=False,
        ).reshape(qm, p, cap) + probe_scores[..., None]
        return scores, doc_ids, valid

    @jax.jit
    def stage_decompress_fused(q, probe_scores, probe_cids):
        # Single pass: no [Q, P, cap, PB] candidate tensor in HBM. On TPU
        # this times the real Pallas kernel; off-TPU the interpret-mode
        # kernel is Python-rate (meaningless wall-clock), so we time the
        # fused jnp reference instead — the emitted impl= label says which.
        v = q[:, :, None] * index.bucket_weights[None, None, :]
        scores = ops.fused_gather_selective_sum(
            index.packed_codes, index.cluster_offsets, index.cluster_sizes,
            probe_cids, probe_scores, v,
            nbits=index.nbits, dim=index.dim, cap=index.cap,
            n_tokens=index.n_tokens, use_kernel=ops.on_tpu(),
        )
        doc_ids, valid = gather_doc_ids(index, probe_cids)
        return scores, doc_ids, valid

    def make_stage_decompress_ragged(cfg_r):
        # Worklist build + flat fused scoring in one stage: the worklist is
        # part of the ragged layout's cost and is timed with it. A factory
        # so the same stage can run under the static worst-case bound and
        # under the query-adaptive bucket.
        @jax.jit
        def stage(q, probe_scores, probe_cids):
            return ragged_flat_candidates(
                index, q, probe_scores, probe_cids,
                dataclasses.replace(
                    cfg_r,
                    gather="fused",
                    executor="kernel" if ops.on_tpu() else "reference",
                ),
            )

        return stage

    stage_decompress_ragged = make_stage_decompress_ragged(config_ragged)

    @jax.jit
    def stage_reduce(scores, doc_ids, valid, mse, qmask):
        qm = scores.shape[0]
        valid = valid & qmask[:, None, None]
        return two_stage_reduce(
            doc_ids.reshape(qm, -1), None, scores.reshape(qm, -1),
            valid.reshape(qm, -1), mse, q_max=qm, k=config.k,
        )

    @functools.partial(jax.jit, static_argnames=("q_max",))
    def stage_reduce_ragged(scores, doc_ids, qtok, valid, mse, qmask, *, q_max):
        valid = valid & qmask[qtok]
        return two_stage_reduce(
            doc_ids, qtok, scores, valid, mse, q_max=q_max, k=config.k,
            pad_to_k=True,
        )

    return (
        stage_select,
        stage_gather,
        stage_decompress,
        stage_decompress_fused,
        stage_decompress_ragged,
        make_stage_decompress_ragged,
        stage_reduce,
        stage_reduce_ragged,
        config_ragged,
    )


def run() -> None:
    enc_params = TokenEncoder.init(jax.random.PRNGKey(0), _ENC)
    enc = jax.jit(lambda t, m: TokenEncoder.encode(enc_params, _ENC, t, m))
    tok = jnp.zeros((1, 32), jnp.int32)
    tok_mask = jnp.ones((1, 32), bool)

    for tier in ("nfcorpus_like", "lifestyle_like", "pooled_like", "zipf_like"):
        corpus, index, q, qmask, rel = get_setup(tier)
        cfg = WarpSearchConfig(nprobe=32, k=100, t_prime=2000, k_impute=64)
        q0, m0 = jnp.asarray(q[0]), jnp.asarray(qmask[0])
        qm = q0.shape[0]

        # Measured per tier (the encoder is tier-independent, but re-timing
        # it per tier records the steady-state dispatch cost instead of
        # re-emitting one stale number three times).
        t_enc = time_fn(enc, tok, tok_mask)

        # --- stage breakdown (Fig. 9) ---
        (s_sel, s_gather, s_dec, s_dec_fused, s_dec_ragged, make_s_dec_ragged,
         s_red, s_red_ragged, cfg_ragged) = _stage_fns(index, cfg)
        sel = s_sel(q0, m0)
        t_sel = time_fn(s_sel, q0, m0)
        dec = s_dec(q0, sel.probe_scores, sel.probe_cids)
        t_dec = time_fn(s_dec, q0, sel.probe_scores, sel.probe_cids)
        t_gather = time_fn(s_gather, sel.probe_cids)
        t_dec_fused = time_fn(s_dec_fused, q0, sel.probe_scores, sel.probe_cids)
        rag = s_dec_ragged(q0, sel.probe_scores, sel.probe_cids)
        t_dec_ragged = time_fn(
            s_dec_ragged, q0, sel.probe_scores, sel.probe_cids
        )
        # Query-adaptive bucket for the measured query: the smallest
        # ladder rung that fits its actual probe tile demand.
        tile = ops.resolve_tile_c(index.cap, cfg_ragged.tile_c, layout="ragged")
        bucket = worklist.pick_bucket(
            cfg_ragged.worklist_buckets,
            worklist.needed_worklist_tiles(
                worklist.probe_tile_counts(sel.probe_sizes, tile)
            ),
        )
        cfg_bucket = dataclasses.replace(
            cfg_ragged, worklist_tiles=bucket, worklist_buckets=None
        )
        s_dec_adaptive = make_s_dec_ragged(cfg_bucket)
        rag_a = s_dec_adaptive(q0, sel.probe_scores, sel.probe_cids)
        t_dec_adaptive = time_fn(
            s_dec_adaptive, q0, sel.probe_scores, sel.probe_cids
        )
        # DMA/compute split of the fused decompression kernels, via the
        # probe carve-outs (bench_autotune.dense_point/ragged_point) at the
        # tile/buffering a plan would resolve for this index. On TPU the
        # split runs at the full measured probe set; off-TPU interpret-mode
        # kernels are Python-rate, so the split is measured at reduced
        # shapes — the split_shapes label records which regime produced it.
        d_choice = ops.resolve_tile_choice(
            index.cap, cfg.tile_c, layout="dense",
            n_tokens=index.n_tokens, nbits=index.nbits, dim=index.dim,
        )
        r_choice = ops.resolve_tile_choice(
            index.cap, cfg_ragged.tile_c, layout="ragged",
            n_tokens=index.n_tokens, nbits=index.nbits, dim=index.dim,
        )
        if ops.on_tpu():
            sp_starts = index.cluster_offsets[sel.probe_cids].astype(jnp.int32)
            sp_sizes = index.cluster_sizes[sel.probe_cids].astype(jnp.int32)
            sp_pscores = sel.probe_scores
            sp_v = q0[:, :, None] * index.bucket_weights[None, None, :]
            split_label, sp_warm, sp_iters = "full", 2, 5
        else:
            sp_starts, sp_sizes, sp_pscores, sp_v = sweep_probe_set(
                index, q, qmask, nprobe=2, qtokens=4
            )
            split_label, sp_warm, sp_iters = "reduced", 1, 2
        sp_dense = dense_point(
            index, sp_starts, sp_sizes, sp_pscores, sp_v,
            tile_c=d_choice.tile_c, buffering=d_choice.buffering,
            warmup=sp_warm, iters=sp_iters,
        )
        sp_ragged = ragged_point(
            index, sp_starts, sp_sizes, sp_pscores, sp_v,
            tile_c=r_choice.tile_c, buffering=r_choice.buffering,
            warmup=sp_warm, iters=sp_iters,
        )

        t_red = time_fn(s_red, dec[0], dec[1], dec[2], sel.mse, m0)
        t_red_ragged = time_fn(
            s_red_ragged, rag[0], rag[1], rag[2], rag[3], sel.mse, m0, q_max=qm
        )
        t_red_adaptive = time_fn(
            s_red_ragged, rag_a[0], rag_a[1], rag_a[2], rag_a[3], sel.mse, m0,
            q_max=qm,
        )

        # Slot occupancy: real candidates in the probed clusters vs what
        # each layout pays for (= the reduction's sort width).
        real_slots = int(
            np.asarray(index.cluster_sizes)[np.asarray(sel.probe_cids)].sum()
        )
        dense_slots = qm * cfg.nprobe * index.cap
        ragged_slots = qm * cfg_ragged.worklist_tiles * tile
        adaptive_slots = qm * bucket * tile

        emit(f"latency/{tier}/query_encoding", t_enc, "stage")
        emit(f"latency/{tier}/candidate_generation", t_sel, "stage=warpselect")
        emit(
            f"latency/{tier}/decompression",
            t_dec,
            f"stage=implicit_two_step;real_slots={real_slots};"
            f"padded_slots={dense_slots};"
            f"occupancy={real_slots / dense_slots:.3f};sort_n={dense_slots};"
            # Two-step has no overlap by construction: the XLA gather
            # materializes the candidate tensor before scoring reads it.
            f"dma_ms={t_gather * 1e3:.3f};"
            f"compute_ms={max(t_dec - t_gather, 0.0) * 1e3:.3f};"
            f"overlap_frac=0.000;split=gather_vs_score",
        )
        b_two, b_fused = candidate_traffic_bytes(index, qm, cfg.nprobe)
        impl = "kernel" if ops.on_tpu() else "jnp_ref"
        emit(
            f"latency/{tier}/decompression_fused",
            t_dec_fused,
            f"stage=fused_gather;impl={impl};fused_bytes={b_fused};"
            f"two_step_bytes={b_two};bytes_ratio={b_two / max(1, b_fused):.2f}x;"
            f"real_slots={real_slots};padded_slots={dense_slots};"
            f"speedup_vs_two_step={t_dec / max(t_dec_fused, 1e-12):.2f}x;"
            f"dma_ms={sp_dense['dma_s'] * 1e3:.3f};"
            f"compute_ms={sp_dense['compute_s'] * 1e3:.3f};"
            f"overlap_frac={sp_dense['overlap_frac']:.3f};"
            f"split_shapes={split_label};split_tile_c={d_choice.tile_c};"
            f"split_buffering={d_choice.buffering}",
        )
        ladder = ",".join(str(b) for b in cfg_ragged.worklist_buckets)
        emit(
            f"latency/{tier}/decompression_ragged",
            t_dec_ragged,
            f"stage=ragged_worklist;impl={impl};tile_c={tile};"
            f"worklist_tiles_total={qm * cfg_ragged.worklist_tiles};"
            f"real_slots={real_slots};padded_slots={ragged_slots};"
            f"occupancy={real_slots / ragged_slots:.3f};"
            f"slots_vs_dense={ragged_slots / dense_slots:.3f}x;"
            f"speedup_vs_two_step={t_dec / max(t_dec_ragged, 1e-12):.2f}x;"
            f"dma_ms={sp_ragged['dma_s'] * 1e3:.3f};"
            f"compute_ms={sp_ragged['compute_s'] * 1e3:.3f};"
            f"overlap_frac={sp_ragged['overlap_frac']:.3f};"
            f"split_shapes={split_label};split_tile_c={r_choice.tile_c};"
            f"split_buffering={r_choice.buffering}",
        )
        emit(
            f"latency/{tier}/decompression_ragged_adaptive",
            t_dec_adaptive,
            f"stage=ragged_worklist_adaptive;impl={impl};tile_c={tile};"
            f"bucket={bucket};static_bound={cfg_ragged.worklist_tiles};"
            f"ladder={ladder};"
            f"real_slots={real_slots};padded_slots={adaptive_slots};"
            f"occupancy={real_slots / adaptive_slots:.3f};"
            f"slots_vs_static_ragged={adaptive_slots / ragged_slots:.3f}x;"
            f"slots_vs_dense={adaptive_slots / dense_slots:.3f}x;"
            # Same per-tile kernel schedule as the static ragged row — the
            # adaptive bucket changes the worklist bound, not the tile DMA
            # pipeline — so the kernel split carries over.
            f"dma_ms={sp_ragged['dma_s'] * 1e3:.3f};"
            f"compute_ms={sp_ragged['compute_s'] * 1e3:.3f};"
            f"overlap_frac={sp_ragged['overlap_frac']:.3f};"
            f"split_shapes={split_label};split_tile_c={r_choice.tile_c};"
            f"split_buffering={r_choice.buffering}",
        )
        emit(
            f"latency/{tier}/scoring",
            t_red,
            f"stage=two_stage_reduce;sort_n={dense_slots}",
        )
        emit(
            f"latency/{tier}/scoring_ragged",
            t_red_ragged,
            f"stage=two_stage_reduce;sort_n={ragged_slots};"
            f"sort_n_vs_dense={ragged_slots / dense_slots:.3f}x;"
            f"speedup_vs_dense_sort={t_red / max(t_red_ragged, 1e-12):.2f}x",
        )
        emit(
            f"latency/{tier}/scoring_ragged_adaptive",
            t_red_adaptive,
            f"stage=two_stage_reduce;sort_n={adaptive_slots};"
            f"bucket={bucket};"
            f"sort_n_vs_static_ragged={adaptive_slots / ragged_slots:.3f}x;"
            f"sort_n_vs_dense={adaptive_slots / dense_slots:.3f}x;"
            f"speedup_vs_dense_sort={t_red / max(t_red_adaptive, 1e-12):.2f}x",
        )

        # --- end-to-end engines (Fig. 1 / Tables 2-3) ---
        # Dispatch through the planned pipeline; the resolved plan (incl.
        # concretized executor/t'/k_impute/layout) is snapshotted next to
        # the numbers so the perf record names what actually ran.
        retriever = Retriever.from_index(index)
        plan = retriever.plan(cfg)
        plan_fused = retriever.plan(
            dataclasses.replace(cfg, gather="fused", executor="auto")
        )
        plan_ragged = retriever.plan(
            dataclasses.replace(cfg, gather="fused", layout="ragged")
        )
        # The ragged plan snapshot names the bucket ladder (describe())
        # AND the bucket the adaptive dispatcher chose for the measured
        # query, so the recorded numbers are reproducible per rung.
        PLANS[tier] = {
            "warp_e2e": plan.describe(),
            "warp_e2e_fused": plan_fused.describe(),
            "warp_e2e_ragged": {
                **plan_ragged.describe(),
                "chosen_bucket": plan_ragged.adaptive_bucket(q0, m0),
            },
        }
        if tier == "zipf_like":
            # Rung distribution of the shared seeded traffic stream (the
            # same stream the serving suite replays), so latency and
            # serving records agree on the traffic → ladder mapping.
            sq, sm, sids = make_query_stream(tier, 64, seed=11, pool=16)
            rung_of: dict[int, int] = {}
            hist: dict[int, int] = {}
            for j in range(len(sids)):
                pid = int(sids[j])
                if pid not in rung_of:
                    rung_of[pid] = plan_ragged.adaptive_bucket(sq[j], sm[j])
                hist[rung_of[pid]] = hist.get(rung_of[pid], 0) + 1
            PLANS[tier]["warp_e2e_ragged"]["stream_rungs"] = {
                str(k): v for k, v in sorted(hist.items())
            }
            emit(
                f"latency/{tier}/stream_rungs", 0.0,
                "|".join(f"{k}:{v}" for k, v in sorted(hist.items())),
            )
        f_warp = lambda: plan.retrieve(q0, m0)
        t_warp = time_fn(lambda: f_warp())
        t_warp_fused = time_fn(lambda: plan_fused.retrieve(q0, m0))
        t_warp_ragged = time_fn(lambda: plan_ragged.retrieve(q0, m0))
        emit(f"latency/{tier}/warp_e2e_fused", t_enc + t_warp_fused,
             f"retrieval_only={t_warp_fused * 1e6:.1f}")
        emit(
            f"latency/{tier}/warp_e2e_ragged",
            t_enc + t_warp_ragged,
            f"retrieval_only={t_warp_ragged * 1e6:.1f};"
            f"speedup_vs_dense_fused={t_warp_fused / max(t_warp_ragged, 1e-12):.2f}x",
        )
        f_plaid = lambda: plaid_style_search(index, q0, m0, cfg)
        t_plaid = time_fn(lambda: f_plaid())
        emb = jnp.asarray(corpus.emb)
        tdi = jnp.asarray(corpus.token_doc_ids)
        kp = min(corpus.n_tokens, 4000)
        f_xtr = lambda: xtr_reference(q0, m0, emb, tdi, k_prime=kp, k=100)
        t_xtr = time_fn(lambda: f_xtr())
        emit(f"latency/{tier}/warp_e2e", t_enc + t_warp, "retrieval_only=%.1f" % (t_warp * 1e6))
        emit(f"latency/{tier}/plaid_style_e2e", t_enc + t_plaid,
             f"speedup_vs_warp={t_plaid / t_warp:.2f}x")
        emit(f"latency/{tier}/xtr_reference_e2e", t_enc + t_xtr,
             f"speedup_warp_over_xtr={t_xtr / t_warp:.2f}x")
