"""Mixture-of-Experts FFN (Mixtral/DBRX style: top-k softmax routing).

Dispatch is the WARP-style static-capacity CSR gather (sort tokens by
expert, gather [E, cap] with masking) rather than the O(T·E·cap) one-hot
dispatch einsum — the latter's dispatch tensor is larger than the expert
activations themselves at production token counts.

Expert weight sharding is configurable:
  - "tp": experts replicated across the model axis, d_ff sharded
          (column/row parallel) — works for any (E, mesh) combination.
  - "ep": experts sharded across the model axis (requires E % axis == 0);
          tokens reach experts via the same gather, XLA inserts the
          all-to-all. (Hillclimb option; "tp" is the baseline.)
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


from repro.models.layers import dense_init

__all__ = ["MoEConfig", "moe_init", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Perf (§Perf hillclimb): dispatch tokens to experts *inside* a
    # shard_map over the data axes, so routing/sort/gather never cross
    # devices — the global-dispatch baseline makes GSPMD all-gather the
    # full activation tensor per layer. Requires moe_weight_mode="tp_only"
    # (experts replicated over data, TP over model).
    local_dispatch: bool = False
    dispatch_data_axes: tuple[str, ...] = ("data",)
    dispatch_model_axis: str = "model"


def moe_init(key, cfg: MoEConfig, d_model: int, d_ff: int) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    e = cfg.n_experts
    s_in = 1.0 / math.sqrt(d_model)
    s_ff = 1.0 / math.sqrt(d_ff)
    return {
        "router": dense_init(kr, d_model, e),
        "gate": jax.random.normal(kg, (e, d_model, d_ff), jnp.float32) * s_in,
        "up": jax.random.normal(ku, (e, d_model, d_ff), jnp.float32) * s_in,
        "down": jax.random.normal(kd, (e, d_ff, d_model), jnp.float32) * s_ff,
    }


def moe_apply(params: dict, cfg: MoEConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [T, D] -> (y [T, D], aux_loss scalar). Caller flattens batch*seq."""
    if cfg.local_dispatch:
        return _moe_apply_local(params, cfg, x)
    return _moe_apply_global(params, cfg, x)


def _moe_apply_global(params: dict, cfg: MoEConfig, x: jax.Array):
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(cfg.capacity_factor * t * k / e))

    router_logits = (x.astype(jnp.float32) @ params["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
    top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # Load-balancing auxiliary loss (Switch-style).
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0
    ) / k
    aux = e * jnp.sum(me * ce)

    # ---- static-capacity dispatch: sort (token, slot) pairs by expert ----
    flat_e = top_e.reshape(-1)  # [T*k]
    flat_tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
    flat_w = top_p.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]

    counts = jnp.bincount(flat_e, length=e)  # [E]
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])[:-1]
    pos = offsets[:, None] + jnp.arange(cap)[None, :]  # [E, cap]
    valid = jnp.arange(cap)[None, :] < counts[:, None]
    pos = jnp.minimum(pos, t * k - 1)

    tok_idx = stok[pos]  # [E, cap]
    gate_w = jnp.where(valid, sw[pos], 0.0)  # [E, cap]

    xe = x[tok_idx]  # [E, cap, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["gate"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, params["up"].astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, params["down"].astype(x.dtype))  # [E, cap, D]

    ye = ye * gate_w[..., None].astype(ye.dtype)
    y = jax.ops.segment_sum(
        ye.reshape(e * cap, d), tok_idx.reshape(-1), num_segments=t
    )
    return y.astype(x.dtype), aux


def _moe_apply_local(params: dict, cfg: MoEConfig, x: jax.Array):
    """shard_map MoE: per-data-shard routing + dispatch, row-parallel
    experts over the model axis; the only collective is the [T_local, D]
    psum of the down-projection partials (Megatron-MoE shape)."""
    from jax.sharding import PartitionSpec as P

    data = cfg.dispatch_data_axes
    model = cfg.dispatch_model_axis

    def local(xl, router_w, gate, up, down):
        t, d = xl.shape
        e, k = cfg.n_experts, cfg.top_k
        cap = max(1, int(cfg.capacity_factor * t * k / e))

        logits = (xl.astype(jnp.float32) @ router_w).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1))
        aux = e * jnp.sum(me * ce)

        flat_e = top_e.reshape(-1)
        flat_tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
        flat_w = top_p.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
        counts = jnp.bincount(flat_e, length=e)
        offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])[:-1]
        pos = jnp.minimum(offsets[:, None] + jnp.arange(cap)[None, :], t * k - 1)
        valid = jnp.arange(cap)[None, :] < counts[:, None]
        tok_idx = stok[pos]
        gate_w = jnp.where(valid, sw[pos], 0.0)

        xe = xl[tok_idx]  # local gather
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, gate.astype(xl.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xe, up.astype(xl.dtype))
        ye = jnp.einsum("ecf,efd->ecd", h, down.astype(xl.dtype))
        ye = ye * gate_w[..., None].astype(ye.dtype)
        y = jax.ops.segment_sum(ye.reshape(e * cap, d), tok_idx.reshape(-1), num_segments=t)
        y = jax.lax.psum(y.astype(jnp.float32), model)  # row-parallel combine
        aux = jax.lax.pmean(jax.lax.pmean(aux, model), data)
        return y.astype(xl.dtype), aux

    fn = jax.shard_map(
        local,
        in_specs=(
            P(data, None),
            P(None, None),
            P(None, None, model),
            P(None, None, model),
            P(None, model, None),
        ),
        out_specs=(P(data, None), P()),
        check_vma=False,
    )
    return fn(x, params["router"]["w"], params["gate"], params["up"], params["down"])
