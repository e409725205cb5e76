"""Dynamic graphs under differential test — the acceptance contract of the
incremental edge-churn path (``graphs.apply_edge_churn`` /
``engine.ragged_edge_cdf_update`` / ``WalkEngine.apply_churn``) and the
walk-continuity rule (``fleet.migrate_walk_nodes``).

The correctness story is *differential*: every incremental update must be
**bitwise identical** to a from-scratch rebuild —

1. the churned CSR core (indptr/indices/degrees, and the padded
   ``neighbors`` tensor on :class:`CSRGraph`) equals ``from_edges`` over
   the churned edge list, batch after batch, for random churn sequences
   (hypothesis-driven when installed, pinned draws always);
2. the incrementally patched flat per-edge CDF equals a from-scratch
   ``ragged_edge_cdf`` build on the rebuilt graph **at the engine's
   recorded ``cdf_width``**, through BOTH row sources (``lipschitz``
   and ``touched_probs``) — and equals the plain ``WalkEngine.from_graph``
   rebuild whenever the churn left the max degree at that width.  The
   width pin is not pedantry: XLA's CPU reductions lane-split by padded
   row width, so the same row probabilities materialized at a different
   max degree differ in the last ulp — bits are a function of
   (values, width).  ``WalkEngine.apply_churn`` therefore patches at the
   sticky ``cdf_width`` and escalates to a full rebuild only when an
   insert outgrows it (tested explicitly below);
3. the churned ragged engine *steps* bitwise-identically to fresh
   engines of all four layouts on the rebuilt graph, at a W that is not
   a block multiple;
4. the batch contract is strict — every malformed batch raises before
   anything is modified;
5. walk continuity across a graph version pins the documented rule:
   surviving walks carry bitwise, displaced walks re-seed via
   ``active[sample_initial_nodes(len(active), W, seed)[w]]``;
6. (slow) the post-churn chain still realizes the dense ``mhlj()`` law —
   chi-square at ~4-sigma — and its update occupancy still matches the
   rebuilt chain's stationary ``pi``;
7. the learned-collaboration-graph loop (``walk_sgd.run_dada``) runs end
   to end through the trainer/fleet stack, and its first round is
   bitwise-identical to a plain ``run_rw_sgd_multi`` call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dependency (requirements-dev.txt)
    given = settings = st = None

from repro.core import (
    MHLJParams,
    WalkEngine,
    apply_edge_churn,
    barabasi_albert,
    from_edges,
    lollipop,
    mh_importance,
    mh_importance_rows_ragged,
    mhlj,
    mixing,
    row_probs_padded,
)
from repro.core import graphs as graphs_mod
from repro.core.engine import ragged_edge_cdf, ragged_edge_cdf_update
from repro.core.walk import empirical_distribution
from repro.data import make_heterogeneous_regression
from repro.walk_sgd import (
    WalkFleet,
    migrate_walk_nodes,
    run_dada,
    run_rw_sgd_multi,
    sample_initial_nodes,
)

PARAMS = MHLJParams(p_j=0.25, p_d=0.5, r=3)


# ---------------------------------------------------------------------------
# Churn-batch generation (shared by the differential and hypothesis tests)
# ---------------------------------------------------------------------------


def _undirected_pairs(core):
    """Canonical non-loop (lo, hi) pairs of a CSR-core graph."""
    n = core.n
    indptr = np.asarray(core.indptr, np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    dst = np.asarray(core.indices, np.int64)
    keep = src < dst
    return np.stack([src[keep], dst[keep]], axis=1)


def _random_churn(core, rng, k_del, k_ins):
    """A random legal churn batch: deletes keep both endpoints at degree
    >= 3 post-batch and the graph connected (halve-and-retry), inserts are
    uniform non-edges.  Either side may come back ``None`` (empty)."""
    n = core.n
    deg = np.asarray(core.degrees, np.int64)
    pairs = _undirected_pairs(core)
    codes = set((pairs[:, 0] * n + pairs[:, 1]).tolist())
    ok = (deg[pairs[:, 0]] >= 4) & (deg[pairs[:, 1]] >= 4)
    cand = pairs[ok]
    dele = None
    k_del = min(k_del, cand.shape[0])
    while k_del:
        sel = rng.choice(cand.shape[0], size=k_del, replace=False)
        try:
            apply_edge_churn(core, delete=cand[sel], check_connectivity=True)
        except ValueError:
            k_del //= 2
            continue
        dele = cand[sel]
        break
    ins = []
    attempts = 0
    while len(ins) < k_ins and attempts < 50 * (k_ins + 1):
        attempts += 1
        a, b = (int(x) for x in rng.integers(0, n, size=2))
        if a == b:
            continue
        lo, hi = min(a, b), max(a, b)
        if lo * n + hi in codes:
            continue
        codes.add(lo * n + hi)
        ins.append((lo, hi))
    return (np.asarray(ins, np.int64) if ins else None), dele


# ---------------------------------------------------------------------------
# 1+2: differential churn sequences — incremental == rebuild, bitwise
# ---------------------------------------------------------------------------


def _check_churn_sequence(seed, k_del, k_ins, batches=3):
    """Random churn sequence on a hub-heavy BA graph; after every batch the
    incremental core, the padded CSR twin AND the incrementally patched
    engine CDFs (both row sources) are bitwise-equal to from-scratch
    rebuilds."""
    g = barabasi_albert(60, 3, seed=seed, layout="csr")
    core = g.to_ragged()
    padded = g
    lips = np.ones(g.n)
    lips[5] = 35.0  # trap node
    lips_j = jnp.asarray(lips, jnp.float32)
    rng = np.random.default_rng(seed + 100)
    eng_lip = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    eng_flat = WalkEngine.from_graph(
        core, PARAMS, row_probs=mh_importance_rows_ragged(core, lips),
        backend="scan", layout="ragged",
    )
    for batch in range(batches):
        ins, dele = _random_churn(core, rng, k_del, k_ins)
        core, churn = apply_edge_churn(core, insert=ins, delete=dele)
        padded, churn_p = apply_edge_churn(padded, insert=ins, delete=dele)
        assert churn.num_edges_after == int(np.asarray(core.degrees).sum())
        core.validate()  # the from-scratch audit passes on the increment
        eng_lip = eng_lip.apply_churn(core, churn, lipschitz=lips_j)
        # production calling pattern (mirrors walk_sgd.run_dada): a
        # touched-rows-restricted buffer unless the batch outgrew the
        # engine's sticky cdf_width, which escalates to a full rebuild
        # and needs every row
        need_full = (
            int(np.asarray(core.degrees).max()) > eng_flat.cdf_width
        )
        eng_flat = eng_flat.apply_churn(
            core, churn,
            touched_probs=mh_importance_rows_ragged(
                core, lips,
                node_ids=None if need_full else churn.touched_rows,
            ),
        )
        assert eng_lip.graph_version == batch + 1
        assert eng_flat.graph_version == batch + 1
        assert eng_lip.cdf_width == eng_flat.cdf_width
        assert eng_lip.cdf_width >= int(np.asarray(core.degrees).max())

        # from-scratch oracle over the churned edge list
        pairs = _undirected_pairs(core)
        rebuilt = from_edges(
            core.n, pairs[:, 0], pairs[:, 1], layout="ragged"
        )
        for got, ref in (
            (core.indptr, rebuilt.indptr),
            (core.indices, rebuilt.indices),
            (core.degrees, rebuilt.degrees),
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        rebuilt_csr = from_edges(
            core.n, pairs[:, 0], pairs[:, 1], layout="csr"
        )
        np.testing.assert_array_equal(padded.neighbors, rebuilt_csr.neighbors)
        np.testing.assert_array_equal(padded.degrees, rebuilt_csr.degrees)

        # from-scratch CDF oracle at the engine's sticky build width —
        # the bits every patched buffer must reproduce exactly
        ref_lip_cdf = ragged_edge_cdf(
            rebuilt.indptr, rebuilt.indices, rebuilt.degrees,
            lipschitz=lips_j, width=eng_lip.cdf_width,
        )
        ref_flat_cdf = ragged_edge_cdf(
            rebuilt.indptr, rebuilt.indices, rebuilt.degrees,
            row_probs=mh_importance_rows_ragged(rebuilt, lips),
            width=eng_flat.cdf_width,
        )
        np.testing.assert_array_equal(
            np.asarray(eng_lip.edge_cdf).view(np.int32),
            np.asarray(ref_lip_cdf).view(np.int32),
        )
        np.testing.assert_array_equal(
            np.asarray(eng_flat.edge_cdf).view(np.int32),
            np.asarray(ref_flat_cdf).view(np.int32),
        )
        # whenever the churn left the max degree at the build width, the
        # plain from_graph rebuild (natural width) is the same oracle —
        # the incremental engine equals a user's from-scratch engine
        if eng_lip.cdf_width == int(np.asarray(rebuilt.degrees).max()):
            ref_lip = WalkEngine.from_graph(
                rebuilt, PARAMS, lipschitz=lips_j, backend="scan",
                layout="ragged",
            )
            np.testing.assert_array_equal(
                np.asarray(eng_lip.edge_cdf).view(np.int32),
                np.asarray(ref_lip.edge_cdf).view(np.int32),
            )


@pytest.mark.parametrize(
    "seed,k_del,k_ins",
    [(1, 5, 5), (2, 8, 0), (3, 0, 8), (4, 1, 1), (5, 0, 0)],
)
def test_churn_differential_pinned(seed, k_del, k_ins):
    """Pinned churn-sequence draws — run with or without hypothesis;
    covers delete-only, insert-only and empty batches."""
    _check_churn_sequence(seed, k_del, k_ins)


if st is not None:

    @given(
        seed=st.integers(0, 7),
        k_del=st.integers(0, 8),
        k_ins=st.integers(0, 8),
    )
    @settings(max_examples=10, deadline=None)
    def test_churn_differential_hypothesis(seed, k_del, k_ins):
        _check_churn_sequence(seed, k_del, k_ins, batches=2)

else:

    @pytest.mark.skip(
        reason="hypothesis not installed (requirements-dev.txt): the "
        "randomized churn-sequence differential test is skipped; pinned "
        "draws still ran"
    )
    def test_churn_differential_hypothesis():
        """Visible placeholder so a missing hypothesis install shows up as
        a skip instead of the test silently vanishing from collection."""


def test_churn_width_change_on_padded_layout():
    """CSRGraph churn where the padded width must grow (inserts exceed the
    old max degree) and then shrink back (deleting the hub edges) stays
    bitwise-equal to the ``from_edges`` rebuild — the width-changed branch
    of the padded patch."""
    g = graphs_mod.ring(12, layout="csr")
    old_width = g.neighbors.shape[1]
    v = 0
    ins = np.asarray(
        [[v, u] for u in (3, 5, 6, 7, 9)], np.int64
    )  # degree 0: 3 -> 8 > old width
    g2, churn = apply_edge_churn(g, insert=ins)
    assert g2.neighbors.shape[1] > old_width
    g2.validate()
    pairs = _undirected_pairs(g2)
    rebuilt = from_edges(g2.n, pairs[:, 0], pairs[:, 1], layout="csr")
    np.testing.assert_array_equal(g2.neighbors, rebuilt.neighbors)
    g3, _ = apply_edge_churn(g2, delete=ins)
    assert g3.neighbors.shape[1] == old_width
    pairs = _undirected_pairs(g3)
    rebuilt3 = from_edges(g3.n, pairs[:, 0], pairs[:, 1], layout="csr")
    np.testing.assert_array_equal(g3.neighbors, rebuilt3.neighbors)
    np.testing.assert_array_equal(g3.indices, g.indices)


def test_churn_width_escalation_rebuilds_at_new_width():
    """Inserting onto the hub pushes the max degree past the engine's
    recorded ``cdf_width``: ``apply_churn`` escalates to a full
    from-scratch rebuild at the new width — bitwise-equal to a plain
    ``from_graph`` rebuild, whose natural width now agrees — and a
    touched-rows-restricted probability buffer is loudly rejected,
    because untouched rows need rebuilding too."""
    g = barabasi_albert(40, 3, seed=4, layout="csr")
    core = g.to_ragged()
    n = core.n
    lips = np.ones(n)
    lips[5] = 35.0
    lips_j = jnp.asarray(lips, jnp.float32)
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    old_width = eng.cdf_width
    assert old_width == int(np.asarray(core.degrees).max())
    indptr = np.asarray(core.indptr, np.int64)
    hub = int(np.asarray(core.degrees, np.int64).argmax())
    nbrs = set(
        np.asarray(core.indices)[indptr[hub] : indptr[hub + 1]].tolist()
    )
    targets = [v for v in range(n) if v != hub and v not in nbrs][:2]
    ins = np.asarray(
        [[min(hub, v), max(hub, v)] for v in targets], np.int64
    )
    core2, churn = apply_edge_churn(core, insert=ins)
    new_max = int(np.asarray(core2.degrees).max())
    assert new_max > old_width

    # a buffer restricted to the touched closure cannot rebuild the
    # untouched rows the width change invalidates
    with pytest.raises(ValueError, match="full-length"):
        eng.apply_churn(
            core2, churn,
            touched_probs=mh_importance_rows_ragged(
                core2, lips, node_ids=churn.touched_rows
            ),
        )

    eng_lip = eng.apply_churn(core2, churn, lipschitz=lips_j)
    assert eng_lip.cdf_width == new_max == eng_lip.max_degree
    assert eng_lip.graph_version == 1
    ref_lip = WalkEngine.from_graph(
        core2, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    np.testing.assert_array_equal(
        np.asarray(eng_lip.edge_cdf).view(np.int32),
        np.asarray(ref_lip.edge_cdf).view(np.int32),
    )

    eng_full = eng.apply_churn(
        core2, churn, touched_probs=mh_importance_rows_ragged(core2, lips)
    )
    ref_flat = WalkEngine.from_graph(
        core2, PARAMS,
        row_probs=mh_importance_rows_ragged(core2, lips),
        backend="scan", layout="ragged",
    )
    np.testing.assert_array_equal(
        np.asarray(eng_full.edge_cdf).view(np.int32),
        np.asarray(ref_flat.edge_cdf).view(np.int32),
    )


@pytest.mark.parametrize("path", ["patch", "escalation"])
def test_churn_cdf_tiles_equal_a_fresh_engine(path):
    """The CDF tile tables the MH move searches follow the churn: after
    ``apply_churn`` — a segment patch when the max degree holds, a full
    rebuild when an insert onto the hub raises it past ``cdf_width`` —
    every level equals, bit for bit, that of a fresh ``from_graph`` on the
    churned graph.  A 151-edge hub gives two search levels."""
    n = 200
    ring = np.arange(n, dtype=np.int64)
    spokes = np.arange(2, 151, dtype=np.int64)
    core = from_edges(
        n,
        np.concatenate([ring, np.zeros_like(spokes)]),
        np.concatenate([(ring + 1) % n, spokes]),
        layout="ragged",
    )
    lips_j = jnp.asarray(
        np.exp(np.random.default_rng(3).normal(0.0, 1.0, n)), jnp.float32
    )
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    assert len(eng.cdf_tiles) == 2
    ins = {"patch": [[50, 120]], "escalation": [[0, 160], [0, 170]]}[path]
    core2, churn = apply_edge_churn(core, insert=np.asarray(ins, np.int64))
    eng2 = eng.apply_churn(core2, churn, lipschitz=lips_j)
    assert (eng2.cdf_width > eng.cdf_width) == (path == "escalation")
    fresh = WalkEngine.from_graph(
        core2, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    assert len(eng2.cdf_tiles) == len(fresh.cdf_tiles) == 2
    for got, want in zip(eng2.cdf_tiles, fresh.cdf_tiles):
        np.testing.assert_array_equal(
            np.asarray(got).view(np.int32), np.asarray(want).view(np.int32)
        )


def test_churn_sticky_width_when_max_degree_drops():
    """Deleting hub edges lowers the graph's max degree; the engine keeps
    its recorded ``cdf_width`` (sticky — never shrinks) and the patched
    CDF matches the from-scratch oracle built at that same width, NOT a
    natural-width rebuild: XLA reduction bits depend on the
    materialization width, so the two oracles legitimately differ."""
    g = barabasi_albert(40, 3, seed=6, layout="csr")
    core = g.to_ragged()
    n = core.n
    lips_j = jnp.asarray(np.ones(n), jnp.float32)
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    w0 = eng.cdf_width
    deg = np.asarray(core.degrees, np.int64)
    hub = int(deg.argmax())
    indptr = np.asarray(core.indptr, np.int64)
    hub_nbrs = np.asarray(core.indices, np.int64)[
        indptr[hub] : indptr[hub + 1]
    ]
    victims = [
        int(v) for v in hub_nbrs if v != hub and deg[v] >= 4
    ][: int(deg[hub]) - 1]
    dele = np.asarray(
        [[min(hub, v), max(hub, v)] for v in victims], np.int64
    )
    core2, churn = apply_edge_churn(core, delete=dele)
    new_max = int(np.asarray(core2.degrees).max())
    assert new_max < w0  # the hub WAS the max and lost enough edges
    eng2 = eng.apply_churn(core2, churn, lipschitz=lips_j)
    assert eng2.cdf_width == w0 and eng2.max_degree == new_max
    oracle = ragged_edge_cdf(
        core2.indptr, core2.indices, core2.degrees,
        lipschitz=lips_j, width=w0,
    )
    np.testing.assert_array_equal(
        np.asarray(eng2.edge_cdf).view(np.int32),
        np.asarray(oracle).view(np.int32),
    )


# ---------------------------------------------------------------------------
# 3: four-layout stepping parity on the churned graph
# ---------------------------------------------------------------------------


def test_four_layout_parity_post_churn():
    """The incrementally churned ragged engine steps bitwise-identically
    to fresh dense/sparse/bucketed/ragged engines built from the rebuilt
    graph — same key, W=37 (not a block multiple).

    The churn here is constrained to preserve the max degree (no pair
    touches a current hub): fresh engines materialize rows at the
    rebuilt graph's natural width, and cross-layout *bitwise* stepping
    parity holds exactly when that width equals the churned engine's
    sticky ``cdf_width`` (XLA reduction bits are width-dependent)."""
    g = barabasi_albert(48, 3, seed=1, layout="csr")
    core = g.to_ragged()
    lips = np.ones(g.n)
    lips[5] = 35.0
    rng = np.random.default_rng(3)
    eng = WalkEngine.from_graph(
        core, PARAMS, row_probs=mh_importance_rows_ragged(core, lips),
        backend="auto", layout="ragged",
    )
    max_deg = int(np.asarray(core.degrees).max())
    for batch in range(2):
        deg = np.asarray(core.degrees, np.int64)
        hub = deg >= max_deg
        ins, dele = _random_churn(core, rng, 4, 4)
        if dele is not None:
            dele = dele[~(hub[dele[:, 0]] | hub[dele[:, 1]])]
            dele = dele if dele.size else None
        if ins is not None:
            ins = ins[~(hub[ins[:, 0]] | hub[ins[:, 1]])]
            ins = ins if ins.size else None
        core, churn = apply_edge_churn(core, insert=ins, delete=dele)
        assert int(np.asarray(core.degrees).max()) == max_deg
        eng = eng.apply_churn(
            core, churn,
            touched_probs=mh_importance_rows_ragged(
                core, lips, node_ids=churn.touched_rows
            ),
        )
    assert eng.cdf_width == max_deg
    pairs = _undirected_pairs(core)
    dense = from_edges(core.n, pairs[:, 0], pairs[:, 1], layout="dense")
    csr = dense.to_csr()
    rp = jnp.asarray(row_probs_padded(mh_importance(dense, lips), dense))
    key = jax.random.PRNGKey(9)
    nodes = jnp.arange(37, dtype=jnp.int32) % core.n
    ref_n, ref_h = eng.step(key, nodes)
    for layout in ("dense", "sparse", "bucketed", "ragged"):
        fresh = WalkEngine.from_graph(
            csr, PARAMS, row_probs=rp, backend="auto", layout=layout
        )
        n2, h2 = fresh.step(key, nodes)
        np.testing.assert_array_equal(np.asarray(ref_n), np.asarray(n2))
        np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(h2))


# ---------------------------------------------------------------------------
# 4: strict batch contract — every malformed batch raises, untouched graph
# ---------------------------------------------------------------------------


def test_churn_contract_errors():
    g = barabasi_albert(30, 3, seed=2, layout="csr")
    core = g.to_ragged()
    pairs = _undirected_pairs(core)
    present = pairs[:1]
    absent = None
    n = core.n
    codes = set((pairs[:, 0] * n + pairs[:, 1]).tolist())
    for a in range(n):
        for b in range(a + 1, n):
            if a * n + b not in codes:
                absent = np.asarray([[a, b]], np.int64)
                break
        if absent is not None:
            break

    with pytest.raises(ValueError, match="already present"):
        apply_edge_churn(core, insert=present)
    with pytest.raises(ValueError, match="not present"):
        apply_edge_churn(core, delete=absent)
    with pytest.raises(ValueError, match="overlap"):
        apply_edge_churn(core, insert=present, delete=present)
    with pytest.raises(ValueError, match="self-loops are structural"):
        apply_edge_churn(core, insert=np.asarray([[3, 3]], np.int64))
    with pytest.raises(ValueError, match="duplicate"):
        apply_edge_churn(
            core, insert=np.concatenate([absent, absent[:, ::-1]])
        )
    with pytest.raises(ValueError):
        apply_edge_churn(core, insert=np.asarray([[0, n]], np.int64))
    with pytest.raises(TypeError, match="to_csr"):
        apply_edge_churn(core.to_dense(), insert=absent)

    # engine-side contract
    lips_j = jnp.asarray(np.ones(n), jnp.float32)
    core2, churn = apply_edge_churn(core, insert=absent)
    eng_sparse = WalkEngine.from_graph(
        g, PARAMS, lipschitz=lips_j, backend="scan", layout="sparse"
    )
    with pytest.raises(ValueError, match="ragged"):
        eng_sparse.apply_churn(core2, churn, lipschitz=lips_j)
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    with pytest.raises(ValueError, match="exactly one"):
        eng.apply_churn(core2, churn)
    with pytest.raises(ValueError, match="exactly one"):
        eng.apply_churn(
            core2, churn, lipschitz=lips_j,
            touched_probs=mh_importance_rows_ragged(
                core2, np.ones(n), node_ids=churn.touched_rows
            ),
        )
    # a touched set that misses a degree-changed row is rejected
    with pytest.raises(ValueError, match="touched"):
        ragged_edge_cdf_update(
            np.asarray(core.indptr, np.int64),
            np.asarray(core.degrees),
            eng.edge_cdf,
            core2.indptr,
            core2.indices,
            core2.degrees,
            np.asarray([], np.int64),
            lipschitz=lips_j,
        )


def test_churn_connectivity_gate():
    """Deleting a path tip's only non-loop edge departs the node; with
    ``check_connectivity=True`` the same batch fails loudly."""
    g = lollipop(6, 3, layout="csr")
    core = g.to_ragged()
    tip = core.n - 1
    nbrs = _undirected_pairs(core)
    tip_edges = nbrs[(nbrs[:, 0] == tip) | (nbrs[:, 1] == tip)]
    assert tip_edges.shape[0] == 1
    with pytest.raises(ValueError, match="disconnects"):
        apply_edge_churn(core, delete=tip_edges, check_connectivity=True)
    core2, churn = apply_edge_churn(core, delete=tip_edges)
    assert int(np.asarray(core2.degrees)[tip]) == 1  # departed: loop only
    assert tip in churn.endpoints and tip in churn.degree_changed


# ---------------------------------------------------------------------------
# 5: walk continuity — the documented re-seed rule, pinned exactly
# ---------------------------------------------------------------------------


def test_walk_continuity_pins_reseed_formula():
    g = lollipop(6, 3, layout="csr")
    core = g.to_ragged()
    tip = core.n - 1
    nbrs = _undirected_pairs(core)
    tip_edges = nbrs[(nbrs[:, 0] == tip) | (nbrs[:, 1] == tip)]
    core2, churn = apply_edge_churn(core, delete=tip_edges)
    deg2 = np.asarray(core2.degrees)

    nodes = np.asarray([0, tip, 3, tip], np.int32)
    new_nodes, displaced = migrate_walk_nodes(nodes, deg2, seed=11)
    np.testing.assert_array_equal(displaced, [False, True, False, True])
    # surviving walks carry their position bitwise
    assert new_nodes[0] == 0 and new_nodes[2] == 3
    # displaced walk w lands on active[sample_initial_nodes(len(active),
    # W, seed)[w]] — THE documented path, nothing else
    active = np.nonzero(deg2 > 1)[0].astype(np.int32)
    draws = sample_initial_nodes(int(active.size), 4, seed=11)
    assert new_nodes[1] == active[draws[1]]
    assert new_nodes[3] == active[draws[3]]
    assert (deg2[new_nodes] > 1).all()

    # fleet-level wiring: engine swap + migration in one call
    lips_j = jnp.asarray(np.ones(core.n), jnp.float32)
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=lips_j, backend="scan", layout="ragged"
    )
    fleet = WalkFleet(
        engine=eng, nodes=jnp.asarray([0, tip], jnp.int32), num_walks=2
    )
    eng2 = eng.apply_churn(core2, churn, lipschitz=lips_j)
    fleet2, disp = fleet.migrate(eng2, seed=11)
    assert fleet2.engine.graph_version == 1
    np.testing.assert_array_equal(disp, [False, True])
    assert int(np.asarray(fleet2.nodes)[0]) == 0
    assert int(np.asarray(fleet2.nodes)[1]) == active[
        sample_initial_nodes(int(active.size), 2, seed=11)[1]
    ]

    with pytest.raises(ValueError, match="out of range"):
        migrate_walk_nodes(np.asarray([core.n + 3]), deg2)
    with pytest.raises(ValueError, match="non-loop"):
        migrate_walk_nodes(nodes, np.ones(core.n, np.int64))


# ---------------------------------------------------------------------------
# 6 (slow): the churned chain still realizes the rebuilt dense law
# ---------------------------------------------------------------------------


def _chi_square_stat(counts, probs, min_expected=10.0):
    total = counts.sum()
    expected = probs * total
    big = expected >= min_expected
    obs = np.concatenate([counts[big], [counts[~big].sum()]])
    exp = np.concatenate([expected[big], [expected[~big].sum()]])
    keep = exp > 0
    obs, exp = obs[keep], exp[keep]
    stat = float(((obs - exp) ** 2 / exp).sum())
    return stat, len(obs) - 1


def _churned_engine_and_dense(seed=1):
    """One churn batch on the BA fixture graph; returns the incremental
    ragged engine and the rebuilt dense twin + lipschitz."""
    g = barabasi_albert(48, 3, seed=seed, layout="csr")
    core = g.to_ragged()
    lips = np.ones(g.n)
    lips[5] = 35.0
    rng = np.random.default_rng(17)
    eng = WalkEngine.from_graph(
        core, PARAMS, lipschitz=jnp.asarray(lips, jnp.float32),
        backend="auto", layout="ragged",
    )
    ins, dele = _random_churn(core, rng, 6, 6)
    core, churn = apply_edge_churn(core, insert=ins, delete=dele)
    eng = eng.apply_churn(core, churn, lipschitz=jnp.asarray(lips, jnp.float32))
    pairs = _undirected_pairs(core)
    dense = from_edges(core.n, pairs[:, 0], pairs[:, 1], layout="dense")
    return eng, dense, lips


@pytest.mark.slow
def test_post_churn_one_step_law_chi_square():
    """The churned engine's one-step empirical law from the trap node
    matches the dense ``mhlj()`` row of the REBUILT graph at ~4-sigma."""
    eng, dense, lips = _churned_engine_and_dense()
    start = 5
    w = 30_000
    nodes = jnp.full((w,), start, jnp.int32)
    expected_row = mhlj(dense, lips, PARAMS)[start]
    nxt, _ = eng.step(jax.random.PRNGKey(23), nodes)
    counts = np.bincount(np.asarray(nxt), minlength=dense.n).astype(np.float64)
    stat, dof = _chi_square_stat(counts, expected_row)
    crit = dof + 4.0 * np.sqrt(2.0 * dof)
    assert stat < crit, f"post-churn chi2={stat:.1f} >= {crit:.1f} (dof={dof})"


@pytest.mark.slow
def test_post_churn_update_occupancy_matches_chain_pi():
    """Long-run update occupancy of the churned engine matches the
    stationary ``pi`` of the rebuilt dense MHLJ chain (TV < 0.08)."""
    eng, dense, lips = _churned_engine_and_dense()
    pi = mixing.stationary_distribution(mhlj(dense, lips, PARAMS))
    num_walks, num_steps = 256, 800
    rng = np.random.default_rng(29)
    nodes = jnp.asarray(
        rng.choice(pi.size, size=num_walks, p=pi), jnp.int32
    )
    occupancy = []
    key = jax.random.PRNGKey(31)
    for _ in range(num_steps):
        key, sub = jax.random.split(key)
        nodes, _ = eng.step(sub, nodes)
        occupancy.append(np.asarray(nodes))
    emp = empirical_distribution(np.stack(occupancy), dense.n)
    tv = mixing.tv_distance(emp, pi)
    assert tv < 0.08, f"post-churn TV(emp, mhlj-pi)={tv:.3f}"


# ---------------------------------------------------------------------------
# 7: the learned-collaboration-graph loop, end to end
# ---------------------------------------------------------------------------


def test_run_dada_end_to_end():
    g = barabasi_albert(40, 3, seed=2, layout="csr")
    data = make_heterogeneous_regression(40, dim=5, seed=3)
    res = run_dada(
        g, data, rounds=3, num_steps=40, num_walks=4, k=3,
        method="mhlj", avg_every=10, seed=5, backend="scan",
    )
    assert res.round_mse.shape == (3,) and np.isfinite(res.round_mse).all()
    assert np.isfinite(res.personalized_mse).all()
    np.testing.assert_array_equal(res.graph_versions, [0, 1, 2])
    assert res.edges_inserted[:-1].sum() > 0  # the graph actually rewires
    assert res.edges_inserted[-1] == 0  # no rewire after the final round
    assert res.x_final.shape == (4, 5)
    # training made progress on the learned graph
    assert res.round_mse[-1] < res.round_mse[0]

    with pytest.raises(ValueError, match="mhlj"):
        run_dada(g, data, method="uniform")


def test_run_dada_round_one_is_plain_trainer():
    """Round 1 of the Dada loop is bitwise-identical to an ordinary
    ``run_rw_sgd_multi`` call on the same seed — the engine seam adds
    nothing to the single-graph path."""
    g = barabasi_albert(40, 3, seed=2, layout="csr")
    data = make_heterogeneous_regression(40, dim=5, seed=3)
    lips = np.asarray(data.lipschitz, np.float64)
    gamma = 0.3 / float(lips.mean())
    params = MHLJParams(p_j=0.1, p_d=0.5, r=3)
    ref = run_rw_sgd_multi(
        "mhlj", g.to_ragged(), data, gamma, 40, 4,
        mhlj_params=params, avg_every=10, seed=5,
    )
    res = run_dada(
        g, data, rounds=1, num_steps=40, num_walks=4, k=3,
        method="mhlj", avg_every=10, seed=5,
    )
    np.testing.assert_array_equal(np.asarray(ref.x_final), res.x_final)
    assert float(ref.avg_mse[-1]) == res.round_mse[0]
