"""Correctness pins for the §Perf optimization paths (EXPERIMENTS.md):
repeat_kv attention == grouped GQA; MoE dispatch constraints don't change
values; weight clipping engages only for the online estimator."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core.graphs import ring
from repro.core.transition import MHLJParams
from repro.models.layers import attention as A
from repro.models.layers import moe as M
from repro.walk_sgd.llm_trainer import WalkContext, init_walk_state


@pytest.mark.parametrize("heads,kv", [(8, 2), (8, 8), (4, 1)])
def test_repeat_kv_matches_grouped(heads, kv):
    dims = A.AttnDims(d_model=128, num_heads=heads, num_kv_heads=kv, head_dim=32)
    params = A.attn_init(jax.random.PRNGKey(0), dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 128))
    for mode, window in (("causal", 0), ("causal", 16), ("bidir", 0)):
        y1 = A.attention_full(params, x, dims, mode=mode, window=window)
        y2 = A.attention_full(
            params, x, dataclasses.replace(dims, repeat_kv=True),
            mode=mode, window=window,
        )
        np.testing.assert_allclose(
            np.asarray(y1), np.asarray(y2), atol=3e-5, rtol=3e-5
        )


def test_maybe_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = A._maybe_constrain(x, ("data", "model"))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_maybe_constrain_skips_indivisible_dims():
    """Under a real mesh, dims that don't divide the axis are dropped (the
    batch-1 decode regression guard) — values unchanged either way."""
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))

    @jax.jit
    def f(x):
        return A._maybe_constrain(x, ("model", None)) * 2.0

    with jax.set_mesh(mesh):
        out = f(jnp.ones((3, 4)))  # 3 % 1 == 0 -> constrained fine
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones((3, 4)))


def test_moe_values_unchanged_by_constraint_gate():
    """cap >= 64 (constraint on) and cap < 64 (off) paths produce identical
    math on one device — the gate is perf-only."""
    dims = M.MoEDims(
        d_model=32, num_experts=4, experts_per_token=2, d_expert=16,
        capacity_factor=8.0,  # large cf -> cap >= 64 for s=32
    )
    params = M.moe_init(jax.random.PRNGKey(0), dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    out1, aux1 = M.moe_apply(params, x, dims)
    dims2 = dataclasses.replace(dims, capacity_factor=1.25)  # cap < 64
    out2, aux2 = M.moe_apply(params, x, dims2)
    # different capacity -> possibly dropped tokens; compare only where no
    # drop occurred in either
    assert bool(jnp.isfinite(out1).all()) and bool(jnp.isfinite(out2).all())
    if float(aux1["moe_dropped_frac"]) == 0.0 == float(aux2["moe_dropped_frac"]):
        np.testing.assert_allclose(
            np.asarray(out1), np.asarray(out2), atol=1e-5, rtol=1e-5
        )


def test_weight_clip_online_only():
    graph = ring(16)
    lips = np.ones(16, np.float32)
    lips[0] = 1000.0  # w(0) = mean/1000 ~ 1/16 = 0.0634 -> clipped to 0.1
    exact = WalkContext.from_graph(graph, MHLJParams(0.1, 0.5, 3))
    online = dataclasses.replace(exact, online_lipschitz=True)
    state = init_walk_state(16, lips, v0=0)
    w_exact = float(exact.weight(state))
    w_online = float(online.weight(state))
    assert w_exact == pytest.approx(np.mean(lips) / 1000.0, rel=1e-4)
    assert w_exact < 0.1
    assert w_online == pytest.approx(0.1)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "mamba2-370m"])
def test_use_kernels_model_path_matches(arch):
    """cfg.use_kernels=True routes attention/SSD through the Pallas kernels
    (interpret mode on CPU) and matches the einsum/jnp path."""
    from repro.configs import get_arch, reduced
    from repro.models.factory import build_model

    cfg = reduced(get_arch(arch))
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    m1 = build_model(cfg, dtype=jnp.float32)
    m2 = build_model(cfg_k, dtype=jnp.float32)
    params = m1.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)), jnp.int32),
    }
    h1 = m1.apply(params, batch)
    h2 = m2.apply(params, batch)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4, rtol=2e-4)
    (l1, _), (l2, _) = m1.loss(params, batch), m2.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
