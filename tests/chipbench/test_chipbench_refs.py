"""The benchmark's inputs and references agree with the program on the
CPU at a small size: the generators make the program's own graphs and
data, the walk reference steps as ``WalkEngine.run`` (scan) does, and the
fleet reference trains as ``run_fleet`` does."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import gen
from chipbench.reference.fleet import reference_call
from chipbench.reference.walk import WalkReference, call_uniforms
from repro.core.engine import WalkEngine
from repro.core.graphs import from_edges, grid2d
from repro.core.transition import MHLJParams
from repro.data.synthetic import make_heterogeneous_regression
from repro.models import regression
from repro.walk_sgd.fleet import WalkFleet, run_fleet

DATA = {"dim": 10, "sigma_low_sq": 1.0, "sigma_high_sq": 100.0, "p_high": 0.002,
        "x_star_scale": 10.0}
CHAIN = {"p_j": 0.1, "p_d": 0.5, "r": 3}


KRON = {"family": "kronecker", "scale": 9, "edgefactor": 16,
        "initiator": [0.57, 0.19, 0.19, 0.05], "graph_seed": 0}


def test_grid_is_the_programs():
    g, p = gen.make_graph({"family": "grid2d", "rows": 20, "cols": 30}), grid2d(
        20, 30, layout="ragged")
    np.testing.assert_array_equal(g.indptr, p.indptr)
    np.testing.assert_array_equal(g.indices, p.indices)
    np.testing.assert_array_equal(g.degrees, p.degrees)


def test_kronecker_csr_is_the_programs_from_its_edges():
    g = gen.make_graph(KRON)
    p = from_edges(g.n, g.src, g.dst, layout="ragged")  # validates connectivity too
    np.testing.assert_array_equal(g.indptr, p.indptr)
    np.testing.assert_array_equal(g.indices, p.indices)
    assert (g.src < g.dst).all() and g.degrees.max() > 10 * np.median(g.degrees)


def test_kronecker_quadrants_follow_the_initiator():
    init = [0.57, 0.19, 0.19, 0.05]
    src, dst = gen._kronecker_bits(10, 1 << 14, init, np.random.default_rng(0))
    bits = np.arange(10)
    s = (src[:, None] >> bits) & 1
    d = (dst[:, None] >> bits) & 1
    seen = [np.mean((s == i) & (d == j)) for i in (0, 1) for j in (0, 1)]
    np.testing.assert_allclose(seen, init, atol=0.005)
    n, s2, d2 = gen._kronecker_edges(4, 16, init, 0)
    assert n == 16 and s2.size == d2.size == 256 and 0 <= s2.min() and s2.max() < 16


def test_largest_component_is_kept_and_relabelled():
    src = np.array([0, 1, 3, 5, 6, 6])
    dst = np.array([2, 2, 4, 5, 1, 2])
    n, s, d = gen._largest_component(7, src, dst)
    assert n == 4  # nodes 0, 1, 2, 6 become 0, 1, 2, 3
    np.testing.assert_array_equal(s, [0, 1, 3, 3])
    np.testing.assert_array_equal(d, [2, 2, 1, 2])


def test_data_is_the_programs():
    d = gen.make_data(500, DATA, np.random.default_rng(9))
    p = make_heterogeneous_regression(500, dim=10, seed=9)
    np.testing.assert_array_equal(d.features, p.features)
    np.testing.assert_array_equal(d.targets, p.targets)
    np.testing.assert_allclose(d.lipschitz, p.lipschitz, rtol=1e-15)


def test_large_seeds_split_into_raw_keys():
    s = gen.split_seed(2**33 + 5)
    key = s.call_key(10**7)  # any call index, with no table to run out of
    assert key.dtype == np.uint32 and key.shape == (2,)
    np.testing.assert_array_equal(key, gen.split_seed(2**33 + 5).call_key(10**7))
    assert not np.array_equal(key, gen.split_seed(2**33 + 6).call_key(10**7))
    assert not np.array_equal(s.call_key(0), s.call_key(1))
    perm = s.call_permutation(3, 50)
    np.testing.assert_array_equal(np.sort(perm), np.arange(50))
    assert not np.array_equal(perm, s.call_permutation(4, 50))


def _setup(family_spec, n_walkers=96):
    g = gen.make_graph(family_spec)
    d = gen.make_data(g.n, DATA, np.random.default_rng(3))
    engine = WalkEngine.from_graph(
        from_edges(g.n, g.src, g.dst, layout="ragged"), MHLJParams(**CHAIN),
        lipschitz=jnp.asarray(d.lipschitz, jnp.float32), layout="ragged",
        backend="scan")
    starts = np.random.default_rng(4).choice(g.n, n_walkers, replace=False)
    return g, d, engine, starts


@pytest.mark.parametrize(
    "family_spec",
    [KRON, {"family": "grid2d", "rows": 30, "cols": 30}],
    ids=["kron", "grid"],
)
def test_walk_reference_steps_as_the_engine(family_spec):
    g, d, engine, starts = _setup(family_spec)
    key = np.array([11, 2**31 + 7], np.uint32)
    nodes, hops = jax.jit(lambda e, k, v: e.run(k, v, 24))(engine, key, starts)
    nodes, hops = np.asarray(nodes), np.asarray(hops)
    u = call_uniforms(key, starts.size, 24, CHAIN["r"])
    ref = WalkReference(g.indptr, g.indices, d.lipschitz, **CHAIN)
    bad, judged = ref.mismatches(nodes, hops, u)
    assert judged == nodes.size and bad == 0
    assert (hops > 1).any()  # jumps were judged too
    # the bfloat16 control does not step as the law does
    ctl = WalkReference(g.indptr, g.indices, d.lipschitz, **CHAIN, dtype=ml_dtypes.bfloat16)
    cbad, cjudged = ref.disagreements(ctl, nodes, u)
    assert cbad / cjudged > 1e-3


def test_fleet_reference_trains_as_run_fleet():
    g, d, engine, starts = _setup(KRON, n_walkers=64)
    lips = d.lipschitz
    gamma = 0.3 / float(lips.mean())
    weights = (lips.mean() / lips).astype(np.float32)
    feats = jnp.asarray(d.features, jnp.float32)
    targs = jnp.asarray(d.targets, jnp.float32)
    fleet = WalkFleet.create(engine, 64, v0s=starts, avg_every=4)
    x = jnp.zeros((64, 10), jnp.float32)
    xr = x
    for call in range(2):
        key = np.array([5, call], np.uint32)
        x, mse, avg, nodes, hops, final = run_fleet(
            key, x, feats, targs, jnp.asarray(weights), fleet, 16, gamma,
            jnp.full((16,), CHAIN["p_j"], jnp.float32), True, regression.linear_grad)
        fleet = WalkFleet(engine=fleet.engine, nodes=final["nodes"], num_walks=64,
                          avg_every=4)
        xr, mse_r, avg_r = reference_call(xr, nodes, feats, targs, jnp.asarray(weights),
                                          gamma, avg_every=4, dtype=jnp.float32, block=32)
        np.testing.assert_allclose(np.asarray(x), np.asarray(xr), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(mse), np.asarray(mse_r), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(avg), np.asarray(avg_r), rtol=1e-5)


def test_walker_losses_at_the_stated_product_precision():
    """With ``loss_inputs=bfloat16`` the per-walker loss is that of the
    product of bfloat16-rounded inputs, summed in float32."""
    from chipbench.reference.fleet import _losses

    rng = np.random.default_rng(5)
    a = rng.normal(size=(300, 10)).astype(np.float32)
    y = rng.normal(size=300).astype(np.float32)
    x = rng.normal(size=(40, 10)).astype(np.float32)
    got = np.asarray(_losses(jnp.asarray(x), jnp.asarray(a), jnp.asarray(y), 16,
                             jnp.bfloat16))
    rb = lambda v: v.astype(ml_dtypes.bfloat16).astype(np.float64)  # noqa: E731
    want = ((y[:, None] - rb(a) @ rb(x).T) ** 2).mean(axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    exact = ((y[:, None] - a.astype(np.float64) @ x.T) ** 2).mean(axis=0)
    assert np.abs(got - exact).max() > 1e-5 * np.abs(exact).max()
