"""MoE layer: routing invariants + local (shard_map) vs global dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.launch.mesh import make_mesh
from repro.models.moe import MoEConfig, moe_apply, moe_init


def test_local_dispatch_matches_global_single_device():
    cfg_g = MoEConfig(n_experts=4, top_k=2)
    cfg_l = MoEConfig(n_experts=4, top_k=2, local_dispatch=True)
    p = moe_init(jax.random.PRNGKey(0), cfg_g, 32, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    y_g, aux_g = moe_apply(p, cfg_g, x)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        y_l, aux_l = jax.jit(lambda p, x: moe_apply(p, cfg_l, x))(p, x)
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_l), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_g), float(aux_l), rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), t=st.integers(4, 64))
def test_moe_output_finite_and_aux_bounded(seed, t):
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0)
    key = jax.random.PRNGKey(seed)
    p = moe_init(key, cfg, 16, 32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, 16))
    y, aux = moe_apply(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()
    # Switch aux loss is >= 1 at perfect balance... actually >= 1 by
    # Cauchy-Schwarz when normalized; just require positive and bounded.
    assert 0.0 < float(aux) < cfg.n_experts * 2


def test_capacity_drops_overflow_tokens():
    """With capacity_factor tiny, overflow tokens contribute zero output."""
    cfg = MoEConfig(n_experts=2, top_k=1, capacity_factor=0.1)
    p = moe_init(jax.random.PRNGKey(0), cfg, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y, _ = moe_apply(p, cfg, x)
    # cap = max(1, 0.1*32*1/2) = 1 -> at most 2 tokens routed
    nonzero_rows = np.asarray(jnp.any(jnp.abs(y) > 0, axis=-1)).sum()
    assert nonzero_rows <= 2
