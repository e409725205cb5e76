"""Unified walk-engine correctness: backend parity + Remark-1 accounting.

The acceptance contract of the engine refactor: the scan backend, the
Pallas (interpret) backend, and the dense ``mhlj()`` matrix chain all
realize the SAME transition law, and the engine's hop counts reproduce the
Remark-1 communication budget.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MHLJParams,
    WalkEngine,
    expected_transitions_per_update,
    mh_importance,
    mhlj,
    p_is_rows,
    remark1_bound,
    row_probs_padded,
    watts_strogatz,
)
from repro.core.walk import graph_tensors


@pytest.fixture(scope="module")
def setup():
    # irregular graph: degree spread + an extreme-Lipschitz trap node
    g = watts_strogatz(50, 4, 0.2, seed=2)
    lips = np.ones(50)
    lips[7] = 40.0
    params = MHLJParams(0.25, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    return g, lips, params, rp


def _engine(g, params, rp, backend):
    return WalkEngine.from_graph(g, params, row_probs=rp, backend=backend)


def _chi_square_stat(counts, probs, min_expected=10.0):
    """Pearson chi-square with small-expectation bins lumped together.

    Returns (stat, dof).  No scipy in the image, so callers compare against
    the normal approximation dof + z * sqrt(2 dof).
    """
    total = counts.sum()
    expected = probs * total
    big = expected >= min_expected
    obs = np.concatenate([counts[big], [counts[~big].sum()]])
    exp = np.concatenate([expected[big], [expected[~big].sum()]])
    keep = exp > 0
    obs, exp = obs[keep], exp[keep]
    stat = float(((obs - exp) ** 2 / exp).sum())
    return stat, len(obs) - 1


def test_backends_bitwise_equal_including_padded_grid(setup):
    """Scan and Pallas backends consume identical uniforms -> identical
    outputs, also when W is not a block multiple (the padded-grid path)."""
    g, lips, params, rp = setup
    key = jax.random.PRNGKey(0)
    for w, block_w in ((128, 64), (300, 128), (37, 256)):
        nodes = jnp.arange(w, dtype=jnp.int32) % g.n
        eng_s = _engine(g, params, rp, "scan")
        eng_p = WalkEngine.from_graph(
            g, params, row_probs=rp, backend="pallas", block_w=block_w
        )
        n_s, h_s = eng_s.step(key, nodes)
        n_p, h_p = eng_p.step(key, nodes)
        np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_p))
        np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_p))


@pytest.mark.slow
def test_backends_match_dense_chain_chi_square(setup):
    """Empirical one-step update-node law of both backends vs the dense
    MHLJ matrix chain, chi-square at ~4-sigma."""
    g, lips, params, rp = setup
    start = 7
    w = 30_000
    nodes = jnp.full((w,), start, jnp.int32)
    expected_row = mhlj(g, lips, params)[start]  # chained-Levy exact law

    for backend in ("scan", "pallas"):
        nxt, _ = _engine(g, params, rp, backend).step(
            jax.random.PRNGKey(11), nodes
        )
        counts = np.bincount(np.asarray(nxt), minlength=g.n).astype(np.float64)
        stat, dof = _chi_square_stat(counts, expected_row)
        crit = dof + 4.0 * np.sqrt(2.0 * dof)
        assert stat < crit, f"{backend}: chi2={stat:.1f} >= {crit:.1f} (dof={dof})"


@pytest.mark.slow
def test_scan_pallas_empirical_distributions_agree(setup):
    """Two-sample chi-square between the backends' own empirical update-node
    distributions (independent keys, so not just bitwise identity)."""
    g, lips, params, rp = setup
    w = 30_000
    nodes = jnp.arange(w, dtype=jnp.int32) % g.n
    n_s, _ = _engine(g, params, rp, "scan").step(jax.random.PRNGKey(3), nodes)
    n_p, _ = _engine(g, params, rp, "pallas").step(jax.random.PRNGKey(4), nodes)
    c_s = np.bincount(np.asarray(n_s), minlength=g.n).astype(np.float64)
    c_p = np.bincount(np.asarray(n_p), minlength=g.n).astype(np.float64)
    pooled = (c_s + c_p) / (2.0 * w)
    stat_s, dof = _chi_square_stat(c_s, pooled)
    stat_p, _ = _chi_square_stat(c_p, pooled)
    crit = dof + 4.0 * np.sqrt(2.0 * dof)
    assert stat_s < crit and stat_p < crit


def test_remark1_hop_accounting(setup):
    """Engine hop counts match expected_transitions_per_update and stay
    within the paper's Remark-1 bound."""
    g, lips, params, rp = setup
    eng = _engine(g, params, rp, "scan")
    v0s = jnp.arange(32, dtype=jnp.int32) % g.n
    _, hops = eng.run(jax.random.PRNGKey(5), v0s, 3_000)
    measured = float(np.asarray(hops, np.float64).mean())
    exact = expected_transitions_per_update(params.p_j, params.p_d, params.r)
    bound = remark1_bound(params.p_j, params.p_d, params.r)
    assert abs(measured - exact) < 0.02
    assert measured <= bound + 0.02


def test_pj_zero_never_jumps(setup):
    g, lips, params, rp = setup
    eng = _engine(g, params, rp, "scan")
    _, hops = eng.run(
        jax.random.PRNGKey(6), jnp.arange(16, dtype=jnp.int32), 500, p_j=0.0
    )
    assert int(np.asarray(hops).max()) == 1


def test_scheduled_pj_anneals_hops(setup):
    """A (T,) p_J schedule flows through the engine (traced, not static)."""
    g, lips, params, rp = setup
    eng = _engine(g, params, rp, "scan")
    sched = jnp.concatenate(
        [jnp.full((500,), 0.5), jnp.zeros((500,))]
    ).astype(jnp.float32)
    _, hops = eng.run(
        jax.random.PRNGKey(7), jnp.arange(8, dtype=jnp.int32), 1_000, p_j=sched
    )
    hops = np.asarray(hops, np.float64)
    assert hops[:, :500].mean() > 1.1
    assert hops[:, 500:].mean() == 1.0


def test_live_rows_match_dense_p_is(setup):
    """Eq.-7 rows computed from a live Lipschitz vector scatter back to the
    dense mh_importance matrix exactly (self mass may spread over pads)."""
    g, lips, params, rp = setup
    dense = mh_importance(g, lips)
    nbrs, degs = graph_tensors(g)
    live = np.asarray(p_is_rows(nbrs, degs, jnp.asarray(lips, jnp.float32)))
    scattered = np.zeros((g.n, g.n))
    nbrs_np = np.asarray(g.neighbors)
    for v in range(g.n):
        np.add.at(scattered[v], nbrs_np[v], live[v])
    np.testing.assert_allclose(scattered, dense, atol=2e-6)


def test_backend_env_var_overrides_auto(setup, monkeypatch):
    """REPRO_BACKEND pins backend="auto" resolution (the CI matrix knob);
    explicit backends ignore it, bogus values fall through to the default."""
    from repro.core.engine import BACKEND_ENV_VAR

    g, lips, params, rp = setup
    eng = WalkEngine.from_graph(g, params, row_probs=rp, backend="auto")
    monkeypatch.setenv(BACKEND_ENV_VAR, "pallas")
    assert eng.resolved_backend == "pallas"
    monkeypatch.setenv(BACKEND_ENV_VAR, "scan")
    assert eng.resolved_backend == "scan"
    monkeypatch.setenv(BACKEND_ENV_VAR, "nonsense")
    assert eng.resolved_backend in ("scan", "pallas")  # platform default
    # explicit backend wins regardless of the env var
    monkeypatch.setenv(BACKEND_ENV_VAR, "pallas")
    explicit = WalkEngine.from_graph(g, params, row_probs=rp, backend="scan")
    assert explicit.resolved_backend == "scan"
    # and the env-pinned engine still samples the same law, bitwise
    nodes = jnp.arange(16, dtype=jnp.int32) % g.n
    key = jax.random.PRNGKey(0)
    n_env, h_env = eng.step(key, nodes)
    monkeypatch.delenv(BACKEND_ENV_VAR)
    n_ref, h_ref = WalkEngine.from_graph(
        g, params, row_probs=rp, backend="pallas"
    ).step(key, nodes)
    np.testing.assert_array_equal(np.asarray(n_env), np.asarray(n_ref))
    np.testing.assert_array_equal(np.asarray(h_env), np.asarray(h_ref))


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_transition_records_its_backend_once_per_trace(setup, monkeypatch, backend):
    """Each trace of a ragged step records the backend it resolved as one
    ``jax.monitoring`` event; a cached call records nothing."""
    from repro.core.engine import BACKEND_ENV_VAR, WALK_TRANSITION_SCOPE, span_event

    g, lips, params, _ = setup
    monkeypatch.delenv(BACKEND_ENV_VAR, raising=False)
    eng = WalkEngine.from_graph(
        g, params, lipschitz=jnp.asarray(lips, jnp.float32), layout="ragged",
        backend=backend, interpret=True if backend == "pallas" else None,
    )
    prefix = span_event(WALK_TRANSITION_SCOPE, "backend_")
    events = []

    def on_event(event, **_):
        if event.startswith(prefix):
            events.append(event)

    step = jax.jit(lambda e, key, nodes: e.step(key, nodes))
    jax.monitoring.register_event_listener(on_event)
    try:
        for w in (8, 8, 16):  # two traces: the second W=8 call hits the cache
            nodes = jnp.arange(w, dtype=jnp.int32) % g.n
            jax.block_until_ready(step(eng, jax.random.PRNGKey(w), nodes))
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    resolved = "scan" if backend == "auto" else "pallas"
    assert events == [f"/repro/walk_transition/backend_{resolved}"] * 2


def _search_case(name):
    """Padded probability rows, matching padded neighbor rows and the
    degrees of one test graph for the tiled ragged search."""
    from repro.core import barabasi_albert, grid2d, star

    rng = np.random.default_rng(11)
    if name == "rows":
        # hand-made rows: degree 1, rows that start or end on 8- and
        # 128-entry tile boundaries, a 500-entry hub, and ~30% zero
        # probabilities (ties in the CDF)
        degrees = np.asarray(
            [1, 7, 8, 1, 111, 128, 256, 1, 3, 500, 5, 1, 64, 2, 1], np.int64
        )
        probs = rng.random((degrees.size, degrees.max()))
        probs[rng.random(probs.shape) < 0.3] = 0.0
        probs[:, 0] += 1e-3  # every row has mass
        probs[np.arange(degrees.max())[None, :] >= degrees[:, None]] = 0.0
        rows = probs / probs.sum(axis=1, keepdims=True)
        neighbors = np.arange(rows.size).reshape(rows.shape) % 977
        return rows.astype(np.float32), neighbors.astype(np.int32), degrees
    g = {
        "star": lambda: star(300),
        "grid": lambda: grid2d(25, 40),
        "ba": lambda: barabasi_albert(200, 3, seed=1),
    }[name]()
    lips = np.exp(rng.normal(0.0, 1.0, g.n))
    rows = row_probs_padded(mh_importance(g, lips), g)
    return rows, np.asarray(g.neighbors), np.asarray(g.degrees, np.int64)


@pytest.mark.parametrize("u_kind", ["zero", "top", "random"])
@pytest.mark.parametrize(
    "name,tile,levels",
    [
        ("star", 8, 3), ("star", 128, 2),
        ("grid", 8, 1), ("grid", 128, 1),
        ("ba", 8, 2), ("ba", 128, 1),
        ("rows", 8, 3), ("rows", 128, 2),
    ],
)
def test_tiled_ragged_search_equals_padded_inversion(name, tile, levels, u_kind):
    """The ragged MH move's tiled lower-bound search over the flat CDF
    (``ragged_mh_invert`` over ``edge_cdf_tiles``) returns, bit for bit,
    the neighbor ``mh_cdf_invert`` picks on the padded row, at 1, 2 and 3
    search levels, for u = 0, u = 1 - ulp and random u."""
    from repro.core.engine import (
        cdf_search_levels,
        edge_cdf_tiles,
        mh_cdf_invert,
        ragged_edge_cdf,
        ragged_mh_invert,
    )

    rows, neighbors, degrees = _search_case(name)
    max_deg = int(degrees.max())
    indptr = np.concatenate([[0], np.cumsum(degrees)])
    keep = np.arange(rows.shape[1])[None, :] < degrees[:, None]
    indices = neighbors[keep]
    edge_cdf = ragged_edge_cdf(indptr, indices, degrees, row_probs=rows)
    tiles = edge_cdf_tiles(edge_cdf, max_degree=max_deg, tile=tile)
    assert len(tiles) == cdf_search_levels(max_deg, tile) == levels
    if name == "rows":  # the boundary rows exist at this tile width
        assert (indptr[1:-1] % tile == 0).any()
        assert (degrees[indptr[:-1] % tile == 0] > 1).any()

    n = degrees.size
    nodes = jnp.asarray(np.tile(np.arange(n), 8), jnp.int32)
    w = nodes.shape[0]
    u = {
        "zero": jnp.zeros((w,), jnp.float32),
        "top": jnp.full((w,), np.nextafter(np.float32(1), np.float32(0))),
        "random": jax.random.uniform(jax.random.PRNGKey(tile + n), (w,)),
    }[u_kind]
    want = mh_cdf_invert(
        jnp.asarray(rows)[nodes], jnp.asarray(neighbors)[nodes], u
    )
    got = jax.jit(ragged_mh_invert)(
        jnp.asarray(indptr, jnp.int32), jnp.asarray(degrees, jnp.int32),
        jnp.asarray(indices, jnp.int32), edge_cdf, tiles, nodes, u,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
