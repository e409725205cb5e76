"""Large-graph MHLJ walk sweep — the scale axis of the ROADMAP north star.

Sweeps batched MHLJ walks over trap-prone CSR topologies up to 1M nodes
and records steps/sec **per engine configuration**: the padded-CSR sparse
layout (rows padded to the global ``max_deg``), the degree-bucketed
layout — both *uncompacted* (every per-bucket pass runs all W walks) and
*compacted* (walks sorted by bucket id per step, each bucket's tile pass
running at its static capacity — the ``engine.bucket_capacities`` rule) —
and the **ragged true-degree layout** (``layout="ragged"``: one flat
per-edge CDF, binary-search MH inversion, no ladder and no compaction
machinery at all).  On hub-heavy families (Barabási–Albert) the padded
layout's resident tables cost O(n·max_deg) — one degree-~10³ hub inflates
every row — the bucketed layout stays O(E + Σ_b n_b·width_b), and the
ragged layout is exactly O(E); compaction removes the bucketed layout's
step-time penalty (per-step MH work drops from W·Σ_b width_b to
Σ_b cap_b·width_b), and the ragged layout drops per-walk row work to
O(log max_deg) outright.  The per-run ``resident_table_bytes`` field
records the memory footprint, ``compact_overflow_rate`` audits the static
capacity rule (fraction of steps whose compacted dispatch overflowed and
fell back — the ``engine.WalkEngine.step`` aux telemetry), and the
per-family ``bucketed_table_shrink`` / ``compaction_step_speedup`` /
``compact_vs_sparse`` / ``ragged_vs_sparse`` / ``ragged_vs_compact``
deriveds summarize the wins (docs/benchmarks.md tells the story).

The full tier additionally runs the ROADMAP's **1M-node Barabási–Albert
sweep in bounded-memory mode**: the graph is built with
``layout="bucketed"`` (the padded ``(n, max_deg)`` table — ~GBs at this
scale — is never materialized, see ``graphs.from_edges``) and only the
bucketed + ragged engine configurations run, so the whole sweep fits a
single host.  The BA family also sweeps the ``bucket_factor`` ladder knob
(factor 4 = coarser ladder, fewer per-bucket passes, more padding).

Everything on this path is O(E): graphs are built as edge lists
(``layout="csr"`` / ``layout="bucketed"``, no N×N adjacency ever exists)
and P_IS rows are the Eq.-7 law computed from local information only.
Graph construction time is recorded per family (``construction_sec``,
also surfaced in ``derived``) so build-path regressions — e.g. the
vectorized Batagelj-style ``barabasi_albert`` sampler rotting back to a
per-node loop — are visible in the JSON.  The smoke tier sweeps **every
registered engine layout** (``repro.core.engine.LAYOUTS``, including the
dense parity layout) plus the compacted bucketed configuration so a
rotted path fails tier-1, not just the default; its derived steps/sec
also feed the CI regression gate (``benchmarks/check_regression.py``).
The JSON result lands in ``results/BENCH_large_graph.json`` (plus the
harness's usual ``bench_large_graph_walk.json``).

The **fleet sweep** (every tier, the ``fleet`` section of the JSON)
measures the mesh-sharded W-walker path of ``repro.walk_sgd.fleet``: the
walker batch is sharded over the ``walker`` logical axis of
``repro.sharding.rules`` (``repro.launch.mesh.make_walker_mesh`` — on
CPU, multi-device only under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and the ragged
engine's ``run`` is timed end to end, recording ``num_walkers`` and
**aggregate** walk-steps/s per fleet size (the ROADMAP's 10M+ aggregate
target is this number), plus a convergence-vs-num-walkers training sweep
through ``repro.walk_sgd.run_rw_sgd_multi`` with periodic averaging —
the arXiv:2604.12260 multi-walker claim (variance term ~1/W, bias floor
unchanged) measured in the same JSON the regression gate watches.  The
fleet rows run on the scan backend: off-TPU the pallas interpret path
would time the interpreter, not the sharded engine, and the gate
normalizes fleet rows against their own smallest-W row
(``benchmarks/check_regression.py``), so the two backends never mix in
one ratio.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR
from repro.core import LAYOUTS, MHLJParams, WalkEngine
from repro.core.graphs import barabasi_albert, dumbbell, grid2d, ring, sbm

NAME = "large_graph_walk"
PAPER_CLAIM = (
    "Scale (beyond-paper): the sparse CSR engine sweeps MHLJ walks over "
    "trap-prone graphs up to 1M nodes in O(E) memory, the degree-bucketed "
    "layout removes the O(n·max_deg) padded-table wall on hub-heavy "
    "topologies, per-step walk compaction removes the bucketed layout's "
    "step-time penalty, and the ragged true-degree layout drops the "
    "bucket ladder entirely (flat per-edge CDF, O(log max_deg) MH "
    "inversion, exactly-O(E) resident state) — no dense N×N transition "
    "table is ever materialized."
)

PARAMS = MHLJParams(p_j=0.1, p_d=0.5, r=3)
TIMED_RUNS = 5  # timed repeats per configuration (see _best_seconds)

# Engine configurations swept per family: label -> from_graph overrides.
# "bucketed" is the uncompacted dispatch (compact=False) so the sweep
# isolates what compaction buys on top of bucketing.
CONFIGS = {
    "sparse": dict(layout="sparse"),
    "dense": dict(layout="dense"),
    "bucketed": dict(layout="bucketed", compact=False),
    "bucketed_compact": dict(layout="bucketed", compact=True),
    "bucketed_compact_f4": dict(layout="bucketed", compact=True,
                                bucket_factor=4),
    "ragged": dict(layout="ragged"),
}


def _families(scale: str):
    """(tag, builder, labels) triples per scale tier.

    ``labels`` picks the engine configurations swept for the family; the
    1M BA entry is bucketed-only (bounded-memory mode: its builder
    returns a ``BucketedCSRGraph`` and the padded table never exists).
    """
    base = ("sparse", "bucketed", "bucketed_compact", "ragged")
    ba = base + ("bucketed_compact_f4",)
    bounded = ("bucketed", "bucketed_compact", "ragged")
    if scale == "smoke":
        # every registered layout + the compacted bucketed path (anti-rot)
        labels = tuple(LAYOUTS) + ("bucketed_compact",)
        return [
            ("ring", lambda: ring(1_500, layout="csr"), labels),
            ("sbm", lambda: sbm([400] * 3, 0.02, 0.002, seed=0, layout="csr"),
             labels),
        ]
    if scale == "quick":
        return [
            ("ring", lambda: ring(8_000, layout="csr"), base),
            ("grid2d", lambda: grid2d(64, 64, layout="csr"), base),
            ("sbm", lambda: sbm([2_000] * 4, 0.005, 0.0002, seed=0,
                                layout="csr"), base),
            ("barabasi_albert", lambda: barabasi_albert(8_000, 3, seed=0,
                                                        layout="csr"), ba),
            ("dumbbell", lambda: dumbbell(128, 4_000, layout="csr"), base),
        ]
    return [
        ("ring", lambda: ring(100_000, layout="csr"), base),
        ("grid2d", lambda: grid2d(316, 316, layout="csr"), base),
        ("sbm", lambda: sbm([25_000] * 4, 0.0008, 0.00002, seed=0,
                            layout="csr"), base),
        ("barabasi_albert", lambda: barabasi_albert(100_000, 3, seed=0,
                                                    layout="csr"), ba),
        ("dumbbell", lambda: dumbbell(256, 99_488, layout="csr"), base),
        # ROADMAP item: the 1M-node hub-heavy sweep.  Bounded-memory mode —
        # built straight into the bucketed layout, padded tables (~8 GB at
        # this max_deg) never exist, only bucketed configs run.
        ("barabasi_albert_1m",
         lambda: barabasi_albert(1_000_000, 3, seed=0, layout="bucketed"),
         bounded),
    ]


def _resident_table_bytes(engine: WalkEngine) -> int:
    """Bytes of per-layout resident row/neighbor state (the thing the
    bucketed layout shrinks); degrees/uniform plumbing are common to all."""
    total = int(engine.degrees.nbytes)
    for field in (engine.neighbors, engine.row_probs, engine.indptr,
                  engine.indices, engine.node_bucket, engine.node_slot,
                  engine.edge_cdf):
        if field is not None:
            total += int(field.nbytes)
    for group in (engine.bucket_neighbors, engine.bucket_rows):
        if group is not None:
            total += sum(int(a.nbytes) for a in group)
    return total


def _best_seconds(calls):
    """Seconds of the fastest of ``TIMED_RUNS`` warm calls of each
    ``(fn, args)`` in ``calls``, and each one's last outputs.  The calls
    are timed round-robin: a smoke-size run lasts about a millisecond, so
    a slow spell of a busy host would otherwise fall on one configuration
    alone and decide the regression gate's ratios."""
    best = [float("inf")] * len(calls)
    outs = [None] * len(calls)
    for _ in range(TIMED_RUNS):
        for i, (fn, args) in enumerate(calls):
            t0 = time.perf_counter()
            outs[i] = jax.block_until_ready(fn(*args))
            best[i] = min(best[i], time.perf_counter() - t0)
    return best, outs


def _prepare_one(
    graph, num_walks: int, num_steps: int, seed: int, label: str,
    backend: str,
):
    """The engine of configuration ``label`` and its compiled, warmed
    trajectory call ``(run, args)``."""
    cfg = dict(CONFIGS[label])
    layout = cfg.pop("layout")
    rng = np.random.default_rng(seed)
    lips = jnp.asarray(
        np.exp(rng.normal(0.0, 1.0, graph.n)), jnp.float32
    )  # heavy-tailed Lipschitz spread: realistic trap pressure
    engine = WalkEngine.from_graph(
        graph, PARAMS, lipschitz=lips, backend=backend, layout=layout, **cfg
    )
    v0s = jnp.asarray(rng.integers(0, graph.n, num_walks), jnp.int32)

    # jit the whole trajectory, exactly like the production consumers
    # (walk_sgd.trainer scans the engine inside one jitted loop) — timing
    # the unjitted path would measure per-call retrace/dispatch overhead,
    # not the engine.  with_aux threads out the per-step compaction
    # telemetry (overflow flags) at no extra cost on the other layouts.
    run = jax.jit(lambda k, v: engine.run(k, v, num_steps, with_aux=True))
    jax.block_until_ready(run(jax.random.PRNGKey(seed), v0s))  # compile + warm
    return engine, (run, (jax.random.PRNGKey(seed + 1), v0s))


def _sweep_family(
    graph, labels, num_walks: int, num_steps: int, seed: int,
    backend: str = "auto",
) -> dict:
    """One result row per configuration in ``labels``, timed together."""
    prepared = [
        _prepare_one(graph, num_walks, num_steps, seed, label, backend)
        for label in labels
    ]
    seconds, outs = _best_seconds([call for _, call in prepared])
    rows = {}
    for label, (engine, _), dt, (_nodes, hops, aux) in zip(
        labels, prepared, seconds, outs
    ):
        layout = CONFIGS[label]["layout"]
        hops_np = np.asarray(hops, np.float64)
        bucketed = layout == "bucketed"
        compacted = bucketed and bool(engine.compact)
        rows[label] = {
            "label": label,
            "layout": layout,
            "compact": bool(engine.compact) if bucketed else None,
            "n": graph.n,
            "nnz": graph.num_edges,
            "max_degree": graph.max_degree,
            "bucket_widths": (
                [nb.shape[1] for nb in engine.bucket_neighbors] if bucketed
                else None
            ),
            "num_walks": num_walks,
            "num_steps": num_steps,
            "walk_steps_per_sec": float(num_walks * num_steps / dt),
            "transitions_per_update": float(hops_np.mean()),
            # fraction of steps whose compacted dispatch overflowed a static
            # bucket capacity and lax.cond fell back to the full-W dispatch
            # — the audit trail of the engine.bucket_capacities rule
            "compact_overflow_rate": (
                float(np.asarray(aux["compact_overflow"], np.float64).mean())
                if compacted else None
            ),
            "resident_table_bytes": _resident_table_bytes(engine),
            "csr_bytes": int(graph.indptr.nbytes + graph.indices.nbytes),
            "dense_table_bytes_avoided": int(graph.n) ** 2 * 8,
        }
    return rows


def _fleet_sweep(scale: str) -> tuple[dict, dict]:
    """Mesh-sharded fleet throughput + convergence-vs-num-walkers sweep.

    Returns ``(fleet_section, derived)``.  Throughput rows time the ragged
    engine's batched ``run`` with the walker batch sharded over the
    ``walker`` logical axis (replication fallback when W doesn't divide
    the mesh) and record **aggregate** walk-steps/s; the convergence rows
    train W walks with periodic averaging through ``run_rw_sgd_multi``
    on the multi-walk benchmark's regression setting and record the
    final averaged-model excess over the least-squares floor — the
    arXiv:2604.12260 ~1/W variance claim, next to the throughput it buys.
    """
    from repro.data import make_heterogeneous_regression
    from repro.launch.mesh import make_walker_mesh
    from repro.sharding.rules import resolve_walker_axis
    from repro.walk_sgd import run_rw_sgd_multi

    mesh = make_walker_mesh()
    n_dev = int(mesh.devices.size)
    fleet_sizes = {
        "smoke": (64, 128), "quick": (1024, 4096), "full": (2048, 8192),
    }[scale]
    num_steps = {"smoke": 30, "quick": 100, "full": 200}[scale]
    if scale == "smoke":
        graph = ring(1_500, layout="csr")
    else:
        graph_n = {"quick": 8_000, "full": 100_000}[scale]
        graph = barabasi_albert(graph_n, 3, seed=0, layout="csr")
    rng = np.random.default_rng(11)
    lips = jnp.asarray(np.exp(rng.normal(0.0, 1.0, graph.n)), jnp.float32)
    # ragged layout on the scan backend: off-TPU the pallas interpret path
    # would time the interpreter, not the sharded engine (module docstring)
    engine = WalkEngine.from_graph(
        graph, PARAMS, lipschitz=lips, backend="scan", layout="ragged"
    )

    fleet: dict = {"mesh_devices": n_dev, "graph_n": graph.n,
                   "layout": "ragged", "backend": "scan"}
    derived: dict = {"fleet_mesh_devices": n_dev}
    calls, sharded = [], []
    for w in fleet_sizes:
        sharding = resolve_walker_axis(w, mesh)
        eng_w = (
            engine.with_walker_sharding(sharding)
            if sharding is not None else engine
        )
        v0s = jnp.asarray(rng.integers(0, graph.n, w), jnp.int32)
        if sharding is not None:
            v0s = jax.device_put(v0s, sharding)
        run_fn = jax.jit(
            lambda k, v, e=eng_w: e.run(k, v, num_steps)
        )
        jax.block_until_ready(run_fn(jax.random.PRNGKey(3), v0s))  # warm
        calls.append((run_fn, (jax.random.PRNGKey(4), v0s)))
        sharded.append(sharding is not None)
    seconds, _ = _best_seconds(calls)
    for w, dt, is_sharded in zip(fleet_sizes, seconds, sharded):
        agg = float(w * num_steps / dt)
        fleet[f"w{w}"] = {
            "num_walkers": w,
            "sharded": is_sharded,
            "aggregate_walk_steps_per_sec": agg,
        }
        derived[f"fleet_w{w}_num_walkers"] = w
        derived[f"fleet_w{w}_aggregate_walk_steps_per_sec"] = agg

    # convergence-vs-num-walkers: same recipe as benchmarks/multi_walk.py,
    # but through the mesh-sharded fleet path with *periodic* averaging
    conv_n = 128
    conv_graph = ring(conv_n)
    data = make_heterogeneous_regression(
        conv_n, dim=6, sigma_high_sq=100.0, p_high=0.03, seed=7,
        x_star_scale=3.0,
    )
    gamma = float(0.3 / data.lipschitz.mean())
    conv_T = {"smoke": 2_000, "quick": 10_000, "full": 20_000}[scale]
    conv_ws = (1, 8) if scale == "smoke" else (1, 2, 4, 8)
    avg_every = 50
    floor = float(data.mse(data.optimum()))
    conv: dict = {}
    for w in conv_ws:
        res = run_rw_sgd_multi(
            "mhlj", conv_graph, data, gamma, conv_T, w,
            mhlj_params=PARAMS, seed=0, avg_every=avg_every, mesh=mesh,
        )
        final = float(res.avg_mse[-1])
        conv[f"w{w}"] = {
            "num_walkers": w,
            "avg_every": avg_every,
            "final_avg_mse": final,
            "excess_over_floor": final - floor,
            "transitions_per_update": res.transitions_per_update,
        }
        derived[f"fleet_conv_w{w}_excess"] = final - floor
    fleet["ls_floor_mse"] = floor
    fleet["convergence_vs_num_walkers"] = conv
    return fleet, derived


def _churn_sweep(scale: str) -> tuple[dict, dict]:
    """Incremental edge churn vs full rebuild on a hub-heavy BA graph.

    One batched churn of 0.1% of the undirected edges (half deletes —
    both endpoints keep degree >= 3, halve-and-retry on disconnect —
    half inserts) is applied two ways.  The batch fraction is the
    scaling knob that decides whether incremental can win at all: an MH
    row reads its neighbors' degrees, so the recompute set is the 1-hop
    closure of the churn endpoints, and on a BA graph edge-uniform
    deletes are hub-biased — the closure amplifies the batch ~25-30x
    (measured at n=100k: 0.1% of edges -> 8% of rows, 1% -> 46%).  By
    ~1% of edges the incremental path is recomputing half the graph and
    necessarily converges to rebuild cost; at 0.1% the O(closure·width)
    patch beats the O(n·width) rebuild by the pinned margin.  The batch
    is applied two ways: (a) the incremental path,
    ``graphs.apply_edge_churn`` + ``WalkEngine.apply_churn`` patching only
    the touched CDF segments, and (b) the from-scratch path,
    ``from_edges(layout="ragged")`` over the churned edge list +
    ``WalkEngine.from_graph``.  Both are warmed once and the second run is
    timed.  The incremental CDF must come out **bitwise identical** to an
    untimed from-scratch oracle built at the engine's recorded
    ``cdf_width`` (``RuntimeError`` otherwise — a fast wrong answer is
    not a speedup).  The width matters: on a BA graph the hub is an
    endpoint of some delete in almost every 1% batch, so the max degree
    drops and a rebuild at the *new* natural width lands on different
    XLA reduction lane splits — 1-ulp CDF diffs on rows the churn never
    touched.  The sticky-width contract (``engine.cdf_width``) is exactly
    what makes the incremental patch sound, and the oracle checks it at
    that width.  ``ba_churn_speedup = rebuild_sec / incremental_sec``
    lands in
    ``derived`` under the presence gate of
    ``benchmarks/check_regression.py`` (wall-clock ratios on the tiny
    smoke batch are too noisy to magnitude-gate).
    """
    from repro.core.graphs import apply_edge_churn, from_edges

    n, m = {
        "smoke": (2_000, 3), "quick": (20_000, 3), "full": (100_000, 3),
    }[scale]
    graph = barabasi_albert(n, m, seed=0, layout="ragged")
    rng = np.random.default_rng(5)
    lips = jnp.asarray(np.exp(rng.normal(0.0, 1.0, n)), jnp.float32)
    engine = WalkEngine.from_graph(
        graph, PARAMS, lipschitz=lips, backend="scan", layout="ragged"
    )

    deg = np.asarray(graph.degrees, np.int64)
    src = np.repeat(
        np.arange(n, dtype=np.int64),
        np.diff(np.asarray(graph.indptr, np.int64)),
    )
    dst = np.asarray(graph.indices, np.int64)
    keep = src < dst
    pairs = np.stack([src[keep], dst[keep]], axis=1)
    budget = max(2, int(0.001 * pairs.shape[0]))
    cand = pairs[(deg[pairs[:, 0]] >= 4) & (deg[pairs[:, 1]] >= 4)]
    k_del = min(budget // 2, cand.shape[0])
    dele = None
    while k_del:
        sel = rng.choice(cand.shape[0], size=k_del, replace=False)
        try:
            apply_edge_churn(
                graph, delete=cand[sel], check_connectivity=True
            )
        except ValueError:
            k_del //= 2
            continue
        dele = cand[sel]
        break
    num_del = 0 if dele is None else dele.shape[0]
    codes = set((pairs[:, 0] * n + pairs[:, 1]).tolist())
    ins = []
    while len(ins) < budget - num_del:
        a, b = (int(x) for x in rng.integers(0, n, size=2))
        if a == b:
            continue
        lo, hi = min(a, b), max(a, b)
        if lo * n + hi in codes:
            continue
        codes.add(lo * n + hi)
        ins.append((lo, hi))
    ins = np.asarray(ins, np.int64)

    def incremental():
        g2, churn = apply_edge_churn(graph, insert=ins, delete=dele)
        eng2 = engine.apply_churn(g2, churn, lipschitz=lips)
        eng2.edge_cdf.block_until_ready()
        return g2, churn, eng2

    # the rebuild path starts from the same churned edge list (extraction
    # is shared state in a real system, so it is timed in neither path)
    g2_warm, churn, eng_inc = incremental()  # warm the block-op jits
    src2 = np.repeat(
        np.arange(n, dtype=np.int64),
        np.diff(np.asarray(g2_warm.indptr, np.int64)),
    )
    dst2 = np.asarray(g2_warm.indices, np.int64)
    keep2 = src2 < dst2

    def rebuild():
        g3 = from_edges(n, src2[keep2], dst2[keep2], layout="ragged")
        eng3 = WalkEngine.from_graph(
            g3, PARAMS, lipschitz=lips, backend="scan", layout="ragged"
        )
        eng3.edge_cdf.block_until_ready()
        return g3, eng3

    rebuild()  # warm
    t0 = time.perf_counter()
    _, _, eng_inc = incremental()
    incremental_sec = time.perf_counter() - t0
    t0 = time.perf_counter()
    g3, eng_reb = rebuild()
    rebuild_sec = time.perf_counter() - t0

    # untimed differential oracle: a from-scratch build at the engine's
    # sticky cdf_width (the timed rebuild above built at the churned
    # graph's own max degree, whose bits legitimately differ when the
    # churn moved the max — see the docstring)
    from repro.core.engine import ragged_edge_cdf

    oracle = ragged_edge_cdf(
        g3.indptr, g3.indices, g3.degrees,
        lipschitz=lips, width=eng_inc.cdf_width,
    )
    same = (
        np.array_equal(np.asarray(g2_warm.indptr), np.asarray(g3.indptr))
        and np.array_equal(
            np.asarray(g2_warm.indices), np.asarray(g3.indices)
        )
        and np.array_equal(
            np.asarray(eng_inc.edge_cdf).view(np.int32),
            np.asarray(oracle).view(np.int32),
        )
    )
    if not same:
        raise RuntimeError(
            "incremental churn diverged bitwise from the from-scratch "
            "same-width oracle — the differential contract is broken, "
            "the timing is meaningless"
        )
    del eng_reb
    speedup = rebuild_sec / incremental_sec
    section = {
        "graph_n": n,
        "num_undirected_edges": int(pairs.shape[0]),
        "batch_inserts": int(ins.shape[0]),
        "batch_deletes": int(num_del),
        "touched_rows": int(churn.touched_rows.size),
        "incremental_sec": incremental_sec,
        "rebuild_sec": rebuild_sec,
        "speedup": speedup,
        "bitwise_equal": True,
    }
    return section, {"ba_churn_speedup": speedup}


def run(quick: bool = False, scale: str | None = None) -> dict:
    scale = scale or ("quick" if quick else "full")
    num_walks = {"smoke": 128, "quick": 1024, "full": 2048}[scale]
    num_steps = {"smoke": 30, "quick": 100, "full": 200}[scale]
    # Smoke must force backend="pallas": under "auto" an off-TPU run
    # resolves to scan and the layouts' kernels would never execute, so a
    # rotted kernel could pass CI.  Off-TPU the pallas backend runs in
    # interpret mode — slow, hence the tiny smoke sizes.
    backend = "pallas" if scale == "smoke" else "auto"
    out = {"claim": PAPER_CLAIM, "scale": scale, "params": vars(PARAMS) | {}}
    derived = {}
    for tag, build, labels in _families(scale):
        t0 = time.perf_counter()
        graph = build()
        build_s = time.perf_counter() - t0
        fam: dict = {"construction_sec": build_s}
        # surfaced in derived too, so a build-path regression (e.g. the
        # vectorized BA sampler rotting back to a per-node loop) is visible
        # where the smoke/regression tooling looks
        derived[f"{tag}_construction_sec"] = build_s
        fam.update(_sweep_family(
            graph, labels, num_walks, num_steps, seed=7, backend=backend,
        ))
        for label in labels:
            derived[f"{tag}_{label}_steps_per_sec"] = (
                fam[label]["walk_steps_per_sec"]
            )
            rate = fam[label].get("compact_overflow_rate")
            if rate is not None:
                derived[f"{tag}_{label}_overflow_rate"] = rate
        if "sparse" in fam and "bucketed" in fam:
            fam["bucketed_step_speedup"] = (
                fam["bucketed"]["walk_steps_per_sec"]
                / fam["sparse"]["walk_steps_per_sec"]
            )
            fam["bucketed_table_shrink"] = (
                fam["sparse"]["resident_table_bytes"]
                / fam["bucketed"]["resident_table_bytes"]
            )
            derived[f"{tag}_bucketed_table_shrink"] = fam["bucketed_table_shrink"]
        if "bucketed" in fam and "bucketed_compact" in fam:
            fam["compaction_step_speedup"] = (
                fam["bucketed_compact"]["walk_steps_per_sec"]
                / fam["bucketed"]["walk_steps_per_sec"]
            )
            derived[f"{tag}_compaction_step_speedup"] = (
                fam["compaction_step_speedup"]
            )
        if "sparse" in fam and "bucketed_compact" in fam:
            fam["compact_vs_sparse"] = (
                fam["bucketed_compact"]["walk_steps_per_sec"]
                / fam["sparse"]["walk_steps_per_sec"]
            )
        if "sparse" in fam and "ragged" in fam:
            fam["ragged_vs_sparse"] = (
                fam["ragged"]["walk_steps_per_sec"]
                / fam["sparse"]["walk_steps_per_sec"]
            )
            fam["ragged_table_shrink"] = (
                fam["sparse"]["resident_table_bytes"]
                / fam["ragged"]["resident_table_bytes"]
            )
        if "bucketed_compact" in fam and "ragged" in fam:
            fam["ragged_vs_compact"] = (
                fam["ragged"]["walk_steps_per_sec"]
                / fam["bucketed_compact"]["walk_steps_per_sec"]
            )
        out[tag] = fam
    fleet, fleet_derived = _fleet_sweep(scale)
    out["fleet"] = fleet
    derived.update(fleet_derived)
    churn, churn_derived = _churn_sweep(scale)
    out["churn"] = churn
    derived.update(churn_derived)
    out["derived"] = derived

    if scale != "smoke":  # don't clobber real sweeps from the anti-rot tier
        os.makedirs(RESULTS_DIR, exist_ok=True)
        path = os.path.join(RESULTS_DIR, "BENCH_large_graph.json")
        # keep the committed smoke-tier regression baseline
        # (benchmarks/check_regression.py --update writes it) across
        # full-sweep refreshes
        if os.path.exists(path):
            with open(path) as f:
                prior = json.load(f)
            if "smoke_baseline" in prior:
                out["smoke_baseline"] = prior["smoke_baseline"]
        with open(path, "w") as f:
            json.dump(out, f, indent=2, default=float)
    return out


def run_smoke() -> dict:
    """Tiny tier exercised by the tier-1 bench-smoke test: every registered
    engine layout (plus the compacted bucketed dispatch) takes real steps
    here, so a broken path fails CI."""
    return run(scale="smoke")
