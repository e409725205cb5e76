"""Sparse- and bucketed-layout engine correctness — the acceptance contract
of the CSR and degree-bucketed refactors.

Four claims:

1. The sparse scan backend, the sparse Pallas tile backend, and the dense
   ``mhlj()`` matrix chain realize the SAME transition law on an irregular
   (CSR-built) graph — chi-square at ~4-sigma.
2. Scan and sparse-Pallas are BITWISE equal given the same key, including
   when ``max_degree`` is odd (not a multiple of any block/lane size) and
   W is not a multiple of ``block_w``.
3. ``layout="bucketed"`` (per-degree-bucket tiles, pallas AND its scan
   fallback) is BITWISE equal to the sparse and dense layouts on hub-heavy
   and trap-prone graphs, including bucket-boundary degrees — so the whole
   chi-square/stationary harness verifies the bucketed path for free.
4. The sparse and bucketed layouts are genuinely O(E)-resident: the full
   (n, max_deg) row table is never materialized on the live-rows path, and
   the bucketed engine carries no full-width tensor at all.
5. Per-step walk compaction (the fast bucketed dispatch: walks sorted by
   bucket id, tile passes at static capacity, overflow -> full-dispatch
   fallback) never changes a sampled walk — bitwise parity with
   layout="sparse" at adversarial shapes: W not a block_w multiple, all
   walks in one bucket, empty buckets, capacity overflow, and both
   bucket_factor ladders.
6. ``layout="ragged"`` (flat per-edge CDF, binary-search MH inversion,
   fused scalar-prefetch kernel) is BITWISE equal to every other layout
   per key — from a shared padded row table, from the flat numpy
   builders, and from a live lipschitz vector; on hub-heavy/trap-prone
   graphs, at bucket-boundary degrees, and at W values that are not
   block multiples — and its resident state is O(E): every engine array
   but the CDF's 128-wide tile tables is one-dimensional (no padded, no
   per-bucket table), and
   ``from_edges(layout="ragged")`` builds a graph that never carries a
   padded tensor at all.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MHLJParams,
    WalkEngine,
    barabasi_albert,
    dumbbell,
    lollipop,
    mh_importance,
    mh_importance_rows_bucketed,
    mh_importance_rows_ragged,
    mhlj,
    row_probs_padded,
    sbm,
)


@pytest.fixture(scope="module")
def setup():
    # irregular hub-heavy graph, built dense for the matrix-chain oracle;
    # the engine consumes its O(E) CSR twin
    g = barabasi_albert(48, 3, seed=1, layout="dense")
    csr = g.to_csr()
    lips = np.ones(g.n)
    lips[5] = 35.0  # trap node
    params = MHLJParams(0.25, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    return g, csr, lips, params, rp


def _engine(csr, params, rp, backend, layout="sparse", block_w=256):
    return WalkEngine.from_graph(
        csr, params, row_probs=rp, backend=backend, layout=layout,
        block_w=block_w,
    )


def _chi_square_stat(counts, probs, min_expected=10.0):
    """Pearson chi-square with small-expectation bins lumped together."""
    total = counts.sum()
    expected = probs * total
    big = expected >= min_expected
    obs = np.concatenate([counts[big], [counts[~big].sum()]])
    exp = np.concatenate([expected[big], [expected[~big].sum()]])
    keep = exp > 0
    obs, exp = obs[keep], exp[keep]
    stat = float(((obs - exp) ** 2 / exp).sum())
    return stat, len(obs) - 1


def test_sparse_backends_bitwise_equal_odd_max_degree(setup):
    """Scan and sparse-Pallas tiles agree bitwise on a CSR graph whose
    max_degree (7) is not a multiple of any block size, across W values
    that are not block multiples either."""
    _, _, _, params, _ = setup
    g = dumbbell(6, 3)  # clique bridge node: deg 7 — odd on purpose
    assert g.max_degree % 2 == 1
    csr = g.to_csr()
    lips = np.ones(g.n)
    lips[0] = 25.0
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    key = jax.random.PRNGKey(0)
    for w, block_w in ((128, 64), (300, 128), (37, 256), (5, 4)):
        nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
        n_s, h_s = _engine(csr, params, rp, "scan").step(key, nodes)
        n_p, h_p = _engine(
            csr, params, rp, "pallas", block_w=block_w
        ).step(key, nodes)
        np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_p))
        np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_p))


def test_sparse_and_dense_layouts_bitwise_equal(setup):
    """The sparse tile kernel and the legacy full-table kernel are the same
    transition, bit for bit."""
    _, csr, _, params, rp = setup
    key = jax.random.PRNGKey(2)
    nodes = jnp.arange(200, dtype=jnp.int32) % csr.n
    n_sp, h_sp = _engine(csr, params, rp, "pallas", layout="sparse").step(key, nodes)
    n_dn, h_dn = _engine(csr, params, rp, "pallas", layout="dense").step(key, nodes)
    np.testing.assert_array_equal(np.asarray(n_sp), np.asarray(n_dn))
    np.testing.assert_array_equal(np.asarray(h_sp), np.asarray(h_dn))


@pytest.mark.slow
def test_sparse_backends_match_dense_chain_chi_square(setup):
    """Empirical one-step law of the sparse scan backend, the sparse Pallas
    backend AND the bucketed layout vs the dense MHLJ matrix chain,
    chi-square at ~4-sigma, on the irregular BA graph."""
    g, csr, lips, params, rp = setup
    start = 5
    w = 30_000
    nodes = jnp.full((w,), start, jnp.int32)
    expected_row = mhlj(g, lips, params)[start]  # chained-Levy exact law

    for backend, layout, key in (
        ("scan", "sparse", 11),
        ("pallas", "sparse", 12),
        ("pallas", "bucketed", 13),
        ("pallas", "ragged", 14),
        ("scan", "ragged", 15),
    ):
        nxt, _ = _engine(csr, params, rp, backend, layout=layout).step(
            jax.random.PRNGKey(key), nodes
        )
        counts = np.bincount(np.asarray(nxt), minlength=csr.n).astype(np.float64)
        stat, dof = _chi_square_stat(counts, expected_row)
        crit = dof + 4.0 * np.sqrt(2.0 * dof)
        assert stat < crit, (
            f"{backend}/{layout}: chi2={stat:.1f} >= {crit:.1f} (dof={dof})"
        )


def test_sparse_layout_never_builds_full_table(setup, monkeypatch):
    """O(E) guarantee: with live Eq.-7 rows, neither sparse backend ever
    calls ``rows_table`` (the dense layout does — sanity-checked last)."""
    _, csr, lips, params, _ = setup
    lips_j = jnp.asarray(lips, jnp.float32)
    nodes = jnp.arange(32, dtype=jnp.int32) % csr.n

    def boom(self, lipschitz=None):
        raise AssertionError("sparse layout materialized the dense row table")

    monkeypatch.setattr(WalkEngine, "rows_table", boom)
    for backend in ("scan", "pallas"):
        eng = WalkEngine.from_graph(
            csr, params, backend=backend, layout="sparse"
        )
        nxt, hops = eng.step(jax.random.PRNGKey(3), nodes, lipschitz=lips_j)
        nxt = np.asarray(nxt)
        assert ((nxt >= 0) & (nxt < csr.n)).all()

    monkeypatch.undo()
    called = {}
    real = WalkEngine.rows_table

    def spying(self, lipschitz=None):
        called["yes"] = True
        return real(self, lipschitz)

    monkeypatch.setattr(WalkEngine, "rows_table", spying)
    eng = WalkEngine.from_graph(csr, params, backend="pallas", layout="dense")
    eng.step(jax.random.PRNGKey(4), nodes, lipschitz=lips_j)
    assert called.get("yes")


# ---------------------------------------------------------------------------
# Degree-bucketed layout parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "build",
    [
        lambda: barabasi_albert(80, 3, seed=3, layout="dense"),
        lambda: lollipop(16, 9),
    ],
)
def test_bucketed_layout_bitwise_equal_all_paths(build):
    """layout='bucketed' — both the per-bucket Pallas tile dispatch and its
    pure-jnp scan fallback — agrees bitwise with layout='sparse' and the
    scan oracle on a hub-heavy BA graph and the lollipop stressor, at W
    values that are not block multiples.  The bucketed engines are driven
    once from the full row table (exact column truncation) and once from
    the per-bucket numpy builders."""
    g = build()
    csr = g.to_csr()
    bg = csr.to_bucketed()
    assert len(bg.buckets) >= 2  # the test must actually dispatch
    lips = np.ones(g.n)
    lips[1] = 30.0
    params = MHLJParams(0.3, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    rows_b = mh_importance_rows_bucketed(bg, lips)
    for w, block_w, key_seed in ((37, 16, 0), (300, 128, 1), (129, 64, 2)):
        key = jax.random.PRNGKey(key_seed)
        nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
        ref_n, ref_h = _engine(csr, params, rp, "scan").step(key, nodes)
        candidates = [
            _engine(csr, params, rp, "pallas", layout="sparse",
                    block_w=block_w),
            _engine(csr, params, rp, "pallas", layout="bucketed",
                    block_w=block_w),
            _engine(csr, params, rp, "scan", layout="bucketed"),
            WalkEngine.from_graph(
                bg, params, row_probs=rows_b, backend="pallas",
                block_w=block_w,
            ),
        ]
        for eng in candidates:
            n2, h2 = eng.step(key, nodes)
            np.testing.assert_array_equal(np.asarray(ref_n), np.asarray(n2))
            np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(h2))


def test_bucketed_engine_carries_no_full_width_tensor():
    """The bucketed engine's resident state is O(E + Σ_b n_b·width_b): no
    (n, max_deg) table exists, and asking for one raises."""
    bg = barabasi_albert(100, 3, seed=5, layout="bucketed")
    params = MHLJParams(0.2, 0.5, 3)
    lips = jnp.ones(bg.n)
    eng = WalkEngine.from_graph(bg, params, lipschitz=lips)
    assert eng.layout == "bucketed"
    assert eng.neighbors is None and eng.row_probs is None
    with pytest.raises(ValueError, match="bucketed layout"):
        eng.rows_table()
    max_deg = bg.max_degree
    for b, nbrs in enumerate(eng.bucket_neighbors):
        assert nbrs.shape[1] == bg.buckets[b].width <= max_deg
    # live-rows path: steps stay in range without any precomputed rows
    eng_live = WalkEngine.from_graph(bg, params, backend="scan")
    nodes = jnp.arange(33, dtype=jnp.int32) % bg.n
    nxt, hops = eng_live.step(
        jax.random.PRNGKey(1), nodes, lipschitz=lips
    )
    nxt = np.asarray(nxt)
    assert ((nxt >= 0) & (nxt < bg.n)).all()
    assert ((np.asarray(hops) >= 1) & (np.asarray(hops) <= params.r)).all()


def test_bucketed_run_matches_sparse_run():
    """Whole trajectories (engine.run) agree bitwise between the sparse and
    bucketed layouts — the property that lets the stationary harness cover
    the bucketed path for free."""
    g = barabasi_albert(48, 3, seed=7, layout="dense")
    csr = g.to_csr()
    lips = np.exp(np.random.default_rng(2).normal(0, 0.5, g.n))
    params = MHLJParams(0.25, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    v0s = jnp.arange(24, dtype=jnp.int32) % csr.n
    key = jax.random.PRNGKey(3)
    n_sp, h_sp = _engine(csr, params, rp, "pallas", layout="sparse").run(
        key, v0s, 100
    )
    n_bk, h_bk = _engine(csr, params, rp, "pallas", layout="bucketed").run(
        key, v0s, 100
    )
    np.testing.assert_array_equal(np.asarray(n_sp), np.asarray(n_bk))
    np.testing.assert_array_equal(np.asarray(h_sp), np.asarray(h_bk))


# ---------------------------------------------------------------------------
# Per-step walk compaction (the fast bucketed dispatch)
# ---------------------------------------------------------------------------


def _parity_vs_sparse(csr, params, rp, nodes, key, **bucketed_kwargs):
    """Assert the bucketed engine (scan + pallas) matches layout='sparse'
    bitwise for this key/node set under the given compaction knobs."""
    ref_n, ref_h = _engine(csr, params, rp, "scan").step(key, nodes)
    for backend in ("scan", "pallas"):
        eng = WalkEngine.from_graph(
            csr, params, row_probs=rp, backend=backend, layout="bucketed",
            **bucketed_kwargs,
        )
        n2, h2 = eng.step(key, nodes)
        np.testing.assert_array_equal(np.asarray(ref_n), np.asarray(n2))
        np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(h2))
        yield eng


def test_compacted_parity_w_not_block_multiple(setup):
    """Compacted dispatch at W values that are not block_w multiples (and
    bucket capacities that are not block multiples either) stays bitwise
    equal to layout='sparse' on the hub-heavy BA graph."""
    _, csr, _, params, rp = setup
    for w, block_w, seed in ((37, 16, 0), (300, 128, 1), (129, 64, 2)):
        key = jax.random.PRNGKey(seed)
        nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
        for eng in _parity_vs_sparse(
            csr, params, rp, nodes, key, block_w=block_w, compact=True
        ):
            assert eng.compact


@pytest.mark.parametrize("bucket_factor", [2, 4])
def test_compacted_parity_bucket_factor(setup, bucket_factor):
    """Both width ladders (factor 2 and 4) sample identical walks."""
    _, csr, _, params, rp = setup
    key = jax.random.PRNGKey(5)
    nodes = jnp.arange(200, dtype=jnp.int32) % csr.n
    list(
        _parity_vs_sparse(
            csr, params, rp, nodes, key,
            compact=True, bucket_factor=bucket_factor,
        )
    )


def test_compacted_all_walks_in_one_bucket(setup):
    """Every walk on the same node: one bucket holds all W walks (its
    capacity clamps to W, the node-share rule would have given far less),
    every other bucket runs an all-slop pass — results still bitwise."""
    _, csr, _, params, rp = setup
    from repro.core import bucket_capacities, compact_plan

    nodes = jnp.full((160,), 5, jnp.int32)  # the trap node, all walks
    key = jax.random.PRNGKey(7)
    for eng in _parity_vs_sparse(csr, params, rp, nodes, key, compact=True):
        caps = bucket_capacities(160, eng.bucket_share, eng.capacity_factor)
        bid = eng.node_bucket[nodes]
        _, _, counts = compact_plan(bid, len(caps))
        counts = np.asarray(counts)
        occupied = np.nonzero(counts)[0]
        assert occupied.size == 1  # genuinely one bucket in play
        assert counts[occupied[0]] == 160
        # ... which means the step only stays compacted if that bucket's
        # capacity clamped up to W; otherwise the fallback ran — either
        # way parity held above.  Assert the empty buckets were real:
        assert (counts[counts == 0].size) == len(caps) - 1


def test_compacted_empty_bucket(setup):
    """Walks placed so at least one bucket is empty (count 0): its pass is
    all capacity slop and scatter_compacted must drop every lane."""
    _, csr, _, params, rp = setup
    from repro.core import compact_plan

    # walks only on low-degree nodes: hub buckets stay empty
    deg = np.asarray(csr.degrees)
    low = np.nonzero(deg <= np.median(deg))[0][:64]
    nodes = jnp.asarray(np.resize(low, 100), jnp.int32)
    key = jax.random.PRNGKey(11)
    for eng in _parity_vs_sparse(csr, params, rp, nodes, key, compact=True):
        _, _, counts = compact_plan(
            eng.node_bucket[nodes], len(eng.bucket_neighbors)
        )
        assert (np.asarray(counts) == 0).any()  # an empty bucket existed


def test_compacted_capacity_overflow_falls_back(setup):
    """A capacity_factor so small that counts exceed caps must trigger the
    uncompacted fallback — verified both by the plan arithmetic and by the
    step staying bitwise-identical to layout='sparse'."""
    _, csr, _, params, rp = setup
    from repro.core import bucket_capacities, compact_plan

    w = 300
    nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
    key = jax.random.PRNGKey(13)
    engines = list(
        _parity_vs_sparse(
            csr, params, rp, nodes, key, compact=True, capacity_factor=1e-6
        )
    )
    eng = engines[0]
    # min_cap floors every capacity at 32 < the dominant bucket's count,
    # so this step overflowed and lax.cond took the full-dispatch branch
    caps = np.asarray(
        bucket_capacities(w, eng.bucket_share, eng.capacity_factor)
    )
    _, _, counts = compact_plan(eng.node_bucket[nodes], len(caps))
    assert (np.asarray(counts) > caps).any()


def test_compacted_run_matches_uncompacted_run(setup):
    """Whole trajectories: compaction changes the schedule of per-bucket
    work, never the sampled walk — engine.run agrees bitwise with both the
    uncompacted bucketed engine and the sparse layout."""
    _, csr, _, params, rp = setup
    v0s = jnp.arange(24, dtype=jnp.int32) % csr.n
    key = jax.random.PRNGKey(17)
    n_sp, h_sp = _engine(csr, params, rp, "pallas", layout="sparse").run(
        key, v0s, 60
    )
    for compact in (False, True):
        eng = WalkEngine.from_graph(
            csr, params, row_probs=rp, backend="pallas", layout="bucketed",
            compact=compact,
        )
        n_bk, h_bk = eng.run(key, v0s, 60)
        np.testing.assert_array_equal(np.asarray(n_sp), np.asarray(n_bk))
        np.testing.assert_array_equal(np.asarray(h_sp), np.asarray(h_bk))


def test_compacted_kernel_oracle_parity(setup):
    """The Pallas compacted dispatch and its ref oracle agree bitwise on
    hand-built compacted tiles, including dropped slop lanes."""
    from repro.core import bucket_capacities, compact_plan
    from repro.kernels.walk_transition.kernel import (
        walk_transition_bucketed_compacted,
    )
    from repro.kernels.walk_transition.ref import (
        walk_transition_bucketed_compacted_ref,
    )

    _, csr, _, params, rp = setup
    eng = WalkEngine.from_graph(
        csr, params, row_probs=rp, backend="scan", layout="bucketed"
    )
    w = 75
    nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
    u_mh = jax.random.uniform(jax.random.PRNGKey(3), (w,))
    caps = bucket_capacities(w, eng.bucket_share, eng.capacity_factor)
    order, starts, counts = compact_plan(
        eng.node_bucket[nodes], len(caps)
    )
    # the engine's own gather convention — the same helper step() uses, so
    # this parity check cannot drift from the production gather
    widx_by, valid_by, rows_by, tiles_by, u_by = (
        eng.compacted_bucket_inputs(nodes, u_mh, caps, order, starts, counts)
    )
    got = walk_transition_bucketed_compacted(
        rows_by, tiles_by, u_by, widx_by, valid_by, w,
        block_w=16, interpret=True,
    )
    want = walk_transition_bucketed_compacted_ref(
        rows_by, tiles_by, u_by, widx_by, valid_by, w
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Ragged true-degree layout (flat per-edge CDF, no ladder)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "build",
    [
        lambda: barabasi_albert(80, 3, seed=3, layout="dense"),
        lambda: lollipop(16, 9),  # clique degree 16 sits on a bucket boundary
        lambda: dumbbell(6, 3),  # odd max_degree (7), no power-of-two help
    ],
)
def test_ragged_layout_bitwise_equal_all_paths(build):
    """layout='ragged' — the fused scalar-prefetch kernel AND its pure-jnp
    binary-search fallback — agrees bitwise with the sparse scan oracle,
    the sparse and dense Pallas layouts and the bucketed dispatch, at W
    values that are not block multiples, on hub-heavy (BA),
    bucket-boundary (lollipop) and odd-max-degree (dumbbell) graphs.  The
    ragged engines are driven once from the shared padded row table (exact
    flatten) and once from the flat numpy builder over a graph that never
    had a padded tensor."""
    g = build()
    csr = g.to_csr()
    rg = csr.to_ragged()
    lips = np.ones(g.n)
    lips[1] = 30.0
    params = MHLJParams(0.3, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    flat = mh_importance_rows_ragged(rg, lips)
    for w, block_w, key_seed in ((37, 16, 0), (300, 128, 1), (129, 64, 2)):
        key = jax.random.PRNGKey(key_seed)
        nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
        ref_n, ref_h = _engine(csr, params, rp, "scan").step(key, nodes)
        candidates = [
            _engine(csr, params, rp, "pallas", layout="sparse",
                    block_w=block_w),
            _engine(csr, params, rp, "pallas", layout="dense",
                    block_w=block_w),
            _engine(csr, params, rp, "pallas", layout="bucketed",
                    block_w=block_w),
            _engine(csr, params, rp, "pallas", layout="ragged",
                    block_w=block_w),
            _engine(csr, params, rp, "scan", layout="ragged"),
            WalkEngine.from_graph(
                rg, params, row_probs=flat, backend="pallas",
                block_w=block_w,
            ),
            WalkEngine.from_graph(
                rg, params, row_probs=flat, backend="scan",
            ),
        ]
        for eng in candidates:
            n2, h2 = eng.step(key, nodes)
            np.testing.assert_array_equal(np.asarray(ref_n), np.asarray(n2))
            np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(h2))


def test_ragged_rows_from_table_flat_builder_and_lipschitz_agree():
    """The three ragged row sources — shared padded table (exact flatten),
    flat numpy builder, live-lipschitz chunked build — produce engines
    whose flat CDFs invert to the identical walk per key (the builder
    chunks through the same block math at the same width, so this is
    bitwise, not approximate).  The numpy-builder source is additionally
    checked entry-for-entry against the padded numpy builder."""
    from repro.core import flat_edge_values, mh_importance_rows

    csr = barabasi_albert(90, 3, seed=9, layout="csr")
    rg = csr.to_ragged()
    lips = np.exp(np.random.default_rng(4).normal(0, 0.7, csr.n))
    params = MHLJParams(0.25, 0.5, 3)
    flat = mh_importance_rows_ragged(rg, lips)
    table = mh_importance_rows(csr, lips)
    np.testing.assert_array_equal(
        flat.view(np.int32),
        flat_edge_values(rg.indptr, rg.degrees, table).view(np.int32),
    )
    key = jax.random.PRNGKey(21)
    nodes = jnp.arange(70, dtype=jnp.int32) % csr.n
    engines = [
        WalkEngine.from_graph(
            rg, params, row_probs=flat, backend="scan"
        ),
        WalkEngine.from_graph(
            csr, params, row_probs=jnp.asarray(table), backend="scan",
            layout="ragged",
        ),
    ]
    results = [eng.step(key, nodes) for eng in engines]
    # live-lipschitz source matches the jnp sparse build it chunks through
    eng_live = WalkEngine.from_graph(
        csr, params, lipschitz=jnp.asarray(lips, jnp.float32),
        backend="scan", layout="ragged",
    )
    eng_live_sparse = WalkEngine.from_graph(
        csr, params, lipschitz=jnp.asarray(lips, jnp.float32),
        backend="scan", layout="sparse",
    )
    n_l, h_l = eng_live.step(key, nodes)
    n_s, h_s = eng_live_sparse.step(key, nodes)
    np.testing.assert_array_equal(np.asarray(n_l), np.asarray(n_s))
    np.testing.assert_array_equal(np.asarray(h_l), np.asarray(h_s))
    for n2, h2 in results[1:]:
        np.testing.assert_array_equal(
            np.asarray(results[0][0]), np.asarray(n2)
        )
        np.testing.assert_array_equal(
            np.asarray(results[0][1]), np.asarray(h2)
        )


def test_ragged_engine_resident_state_is_exactly_o_e():
    """The exactly-O(E) guarantee: a ragged engine carries no padded and
    no per-bucket table — every array leaf but the CDF tile tables is
    one-dimensional with at most nnz + n + 1 entries, and the tile tables
    are the flat CDF itself viewed in rows of ``CDF_TILE`` plus at most two
    tiles of padding — and a ``from_edges(layout='ragged')`` graph never
    holds a padded tensor at all.  Asking for full-width rows raises."""
    from repro.core import from_edges
    from repro.core.engine import CDF_TILE

    idx = np.arange(200, dtype=np.int64)
    graph = from_edges(
        200, idx, (idx + 1) % 200, name="ring-ragged", layout="ragged"
    )
    assert not hasattr(graph, "neighbors")  # the padded tensor never exists
    assert not hasattr(graph, "buckets")
    params = MHLJParams(0.2, 0.5, 3)
    lips = jnp.ones(graph.n)
    eng = WalkEngine.from_graph(graph, params, lipschitz=lips)
    assert eng.layout == "ragged"
    assert eng.neighbors is None and eng.row_probs is None
    assert eng.bucket_neighbors is None and eng.bucket_rows is None
    nnz, n = graph.num_edges, graph.n
    for leaf in jax.tree_util.tree_leaves(
        dataclasses.replace(eng, cdf_tiles=None)
    ):
        assert jnp.ndim(leaf) <= 1  # nothing padded, nothing bucketed
        assert jnp.size(leaf) <= nnz + n + 1
    assert int(eng.edge_cdf.shape[0]) == nnz  # the O(E) row state, exactly
    (tiles,) = eng.cdf_tiles  # max degree 3: one search level
    assert tiles.shape[1] == CDF_TILE and tiles.size <= nnz + 2 * CDF_TILE
    np.testing.assert_array_equal(
        np.asarray(tiles).reshape(-1)[:nnz], np.asarray(eng.edge_cdf)
    )
    with pytest.raises(ValueError, match="ragged layout"):
        eng.rows_table()
    with pytest.raises(ValueError, match="ragged layout"):
        eng.rows_for(jnp.arange(4, dtype=jnp.int32))
    # ragged precomputes its CDF at construction: a row-source-less build
    # fails loudly instead of deferring to a live path that cannot exist
    with pytest.raises(ValueError, match="precomputes its flat per-edge CDF"):
        WalkEngine.from_graph(graph, params, layout="ragged")
    nodes = jnp.arange(33, dtype=jnp.int32) % graph.n
    nxt, hops = eng.step(jax.random.PRNGKey(1), nodes)
    nxt = np.asarray(nxt)
    assert ((nxt >= 0) & (nxt < graph.n)).all()
    assert ((np.asarray(hops) >= 1) & (np.asarray(hops) <= params.r)).all()


def test_ragged_run_matches_sparse_run():
    """Whole trajectories (engine.run) agree bitwise between the sparse
    and ragged layouts — so the stationary/chi-square harness covers the
    ragged path exactly as it covers the others."""
    g = barabasi_albert(48, 3, seed=7, layout="dense")
    csr = g.to_csr()
    lips = np.exp(np.random.default_rng(2).normal(0, 0.5, g.n))
    params = MHLJParams(0.25, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    v0s = jnp.arange(24, dtype=jnp.int32) % csr.n
    key = jax.random.PRNGKey(3)
    n_sp, h_sp = _engine(csr, params, rp, "pallas", layout="sparse").run(
        key, v0s, 100
    )
    for backend in ("pallas", "scan"):
        n_rg, h_rg, aux = _engine(
            csr, params, rp, backend, layout="ragged"
        ).run(key, v0s, 100, with_aux=True)
        np.testing.assert_array_equal(np.asarray(n_sp), np.asarray(n_rg))
        np.testing.assert_array_equal(np.asarray(h_sp), np.asarray(h_rg))
        # no ladder -> no compaction -> the overflow telemetry is all-False
        assert not np.asarray(aux["compact_overflow"]).any()


def test_ragged_kernel_oracle_parity():
    """The fused scalar-prefetch kernel and its ref oracle agree bitwise
    on hand-built flat inputs, including W not a block multiple (padded
    kernel lanes sliced off)."""
    from repro.core import ragged_edge_cdf
    from repro.kernels.walk_transition.kernel import walk_transition_ragged
    from repro.kernels.walk_transition.ref import walk_transition_ragged_ref

    g = lollipop(12, 7)
    csr = g.to_csr()
    lips = np.ones(g.n)
    lips[2] = 20.0
    rp = row_probs_padded(mh_importance(g, lips), g)
    indptr = jnp.asarray(csr.indptr, jnp.int32)
    indices = jnp.asarray(csr.indices, jnp.int32)
    degrees = jnp.asarray(csr.degrees, jnp.int32)
    edge_cdf = ragged_edge_cdf(
        csr.indptr, csr.indices, csr.degrees, row_probs=rp
    )
    p_d, r = 0.5, 3
    w = 75  # not a multiple of block_w=16
    nodes = jnp.arange(w, dtype=jnp.int32) % csr.n
    u = jax.random.uniform(jax.random.PRNGKey(5), (w, 3 + r))
    u = u.at[:, 0].set((u[:, 0] < 0.3).astype(jnp.float32))
    got = walk_transition_ragged(
        nodes, indptr, indices, edge_cdf, u,
        p_d=p_d, r=r, block_w=16, interpret=True,
    )
    want = walk_transition_ragged_ref(
        nodes, indptr, degrees, indices, edge_cdf, u,
        p_d=p_d, r=r, max_degree=csr.max_degree,
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_ragged_overflow_telemetry_surfaces_compaction_fallbacks():
    """step/run aux telemetry: a compacted bucketed engine with starved
    capacities reports compact_overflow=True (the step that lax.cond'ed to
    the full dispatch), a healthy one reports False — so the static
    capacity rule is auditable from production sweeps."""
    g = barabasi_albert(48, 3, seed=1, layout="dense")
    csr = g.to_csr()
    lips = np.ones(g.n)
    params = MHLJParams(0.25, 0.5, 3)
    rp = jnp.asarray(row_probs_padded(mh_importance(g, lips), g))
    nodes = jnp.arange(300, dtype=jnp.int32) % csr.n
    key = jax.random.PRNGKey(13)
    starved = WalkEngine.from_graph(
        csr, params, row_probs=rp, backend="scan", layout="bucketed",
        capacity_factor=1e-6,
    )
    _, _, aux = starved.step(key, nodes, with_aux=True)
    assert bool(aux["compact_overflow"])
    healthy = WalkEngine.from_graph(
        csr, params, row_probs=rp, backend="scan", layout="bucketed"
    )
    _, _, aux = healthy.step(key, nodes, with_aux=True)
    assert not bool(aux["compact_overflow"])
    # run() stacks the per-step flags
    _, _, aux = healthy.run(key, nodes[:16], 20, with_aux=True)
    assert np.asarray(aux["compact_overflow"]).shape == (20,)


def test_pure_csr_graph_end_to_end():
    """A graph that never had a dense form (from_edges csr layout) drives
    the engine: nodes stay in range and Remark-1 hops stay in [1, r]."""
    csr = sbm([40, 40, 40], 0.2, 0.01, seed=3, layout="csr")
    params = MHLJParams(0.3, 0.5, 4)
    rng = np.random.default_rng(0)
    lips = jnp.asarray(np.exp(rng.normal(0, 1, csr.n)), jnp.float32)
    eng = WalkEngine.from_graph(
        csr, params, lipschitz=lips, backend="scan", layout="sparse"
    )
    v0s = jnp.asarray(rng.integers(0, csr.n, 64), jnp.int32)
    nodes, hops = eng.run(jax.random.PRNGKey(9), v0s, 300)
    nodes, hops = np.asarray(nodes), np.asarray(hops)
    assert ((nodes >= 0) & (nodes < csr.n)).all()
    assert ((hops >= 1) & (hops <= params.r)).all()
