"""Batched MHLJ walk engine — THE single implementation of Algorithm 1.

Every consumer of the paper's MHLJ transition (the §II.C simulators in
``core.walk``, the regression trainer ``walk_sgd.trainer``, the pjit LLM
orchestrator ``walk_sgd.llm_trainer.WalkContext``, the multi-walk runner
``walk_sgd.multi_walk`` and the ``benchmarks/`` entry points) routes through
this module, so the chain law that Theorem 1 attaches to is sampled by
exactly one piece of code.

Design
------
A transition for W parallel walks consumes a pre-drawn uniform block of
shape ``(W, 3 + r)`` with slot layout::

    [jump_flag, mh, distance, hop_1 .. hop_r]
     U_JUMP     U_MH  U_DIST   U_HOP0 ..

Each stochastic decision owns its own slot (the seed implementations shared
one key/uniform between the MH draw and the jump machinery — benign for the
marginal law because the branches are exclusive, but wrong as documented and
a trap for anything consuming both branches).  The Bernoulli(p_J) jump
decision is resolved *outside* the backends — slot ``U_JUMP`` arrives as a
{0.0, 1.0} flag — which is what lets ``p_j`` be a traced scalar (Fig 6
annealing schedules) while the Pallas kernel keeps only truly-static
compile-time parameters.

Backends (identical law, bitwise-identical outputs given the same key):

* ``"scan"``   — pure JAX ``vmap`` over walks; also the oracle for kernel
  tests.  Gathers only the W active P_IS rows, so it stays cheap for
  single-walk training loops.
* ``"pallas"`` — the ``kernels/walk_transition`` Pallas kernels, in
  interpret mode off-TPU.  Row handling is governed by ``layout``:
  ``"sparse"`` (default) gathers only the W active ``[block_w, max_deg]``
  neighbor tiles and runs the MH CDF inversion in
  ``walk_transition_sparse`` with the Lévy hop chain as O(W) XLA gathers —
  working set O(W·max_deg + E), so 100k-node graphs fit; ``"bucketed"``
  dispatches the same tile kernel per degree bucket of a
  ``graphs.BucketedCSRGraph`` (geometric width ladder, ``bucket_factor``
  2 or 4) with the Lévy hops gathered straight from the CSR arrays,
  dropping the resident tables from O(n·max_deg) to
  O(E + Σ_b n_b·width_b) — the hub-heavy-graph path.  By default the
  bucketed dispatch is *compacted* per step: a stable sort groups the W
  walk indices by bucket id, each bucket's tile pass runs at a static
  capacity (:func:`bucket_capacities`) instead of all W lanes, and
  results scatter back to walk order (:func:`scatter_compacted`) — so
  per-step MH work is Σ_b cap_b·width_b rather than W·Σ_b width_b, with
  a ``lax.cond`` fallback to the full dispatch on capacity overflow;
  ``"ragged"`` is the true-degree layout — resident row state is one flat
  per-edge CDF buffer aligned with the CSR ``indices`` (exactly O(E), no
  padded and no per-bucket table), the MH inversion is a lower-bound
  search of each walk's own CDF segment over aligned 128-wide tiles of
  that buffer (:func:`ragged_mh_invert`: one W-wide gather of whole tiles
  per level, one level while max_deg <= 129 and two up to 16,512, instead
  of O(W·max_deg) row work), and the pallas path is one fused
  scalar-core kernel per walk tile
  (``kernels.walk_transition.walk_transition_ragged``) that performs the
  inversion, the r-hop Lévy gather and the jump/MH combine in a single
  pass — no bucket ladder, no compaction argsort/scatter, no overflow
  ``lax.cond``, and none of the O(W) XLA gather round-trips the other
  sparse layouts leave between kernel and engine; ``"dense"`` keeps the
  original full-table-in-VMEM kernel for parity testing at orchestration
  scale (n <= a few thousand).  The registered layouts live in
  :data:`LAYOUTS`.
* ``"auto"``   — scan (XLA) on every layout, TPU included.  The chip
  chose it: on a TPU v5e the ragged layout's W-wide XLA gathers beat its
  one-walk-at-a-time scalar-core kernel in every cell of ``chipbench/``
  (the kernel had been 92-99.95% of device time; ``PERF_LEDGER.jsonl``
  and ``PERF.md`` §6 hold the numbers).  :data:`TPU_PALLAS_LAYOUTS` lists
  the layouts whose kernel the TPU compiler accepts (ragged only — the
  sparse, bucketed and dense kernels take a ``cumsum`` Mosaic does not
  lower), so an explicit ``"pallas"`` runs compiled there and raises on
  the others instead of dropping to interpret mode
  (:attr:`WalkEngine.resolved_backend`).  Overridable via the
  ``REPRO_BACKEND`` environment variable (:data:`BACKEND_ENV_VAR`),
  which is how the CI matrix forces each backend.  The scan backend also
  services the bucketed layout (pure-jnp per-bucket dispatch, compacted
  the same way), so the bucketed path runs everywhere the engine runs.

P_IS rows (Eq. 7) come either precomputed (``row_probs`` from
``transition.row_probs_padded`` / ``transition.mh_importance_rows``, or a
per-bucket tuple from ``transition.mh_importance_rows_bucketed``) or on
the fly from a live Lipschitz vector (the online-estimator path of
``llm_trainer``) via :func:`p_is_rows`, which needs only local information
(deg(v), deg(u), L_v, L_u).  Rows follow the padded-row convention of
``core.transition``: every true neighbor slot (including the single self
slot) carries its probability, leftover MH mass lands on the self slot,
pads carry exactly 0.  Because pads are exact zeros, a row truncated to
its degree bucket's width has the same CDF prefix bit for bit — the
property that makes ``layout="bucketed"`` agree with the other layouts
per key (see docs/layouts.md).

Remark-1 accounting: every step returns the physical hop count taken per
walk (1 for an MH move, d for a Lévy jump).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_mod
from repro.core.levy import trunc_geom_icdf

__all__ = [
    "U_JUMP",
    "U_MH",
    "U_DIST",
    "U_HOP0",
    "LAYOUTS",
    "TPU_PALLAS_LAYOUTS",
    "BACKEND_ENV_VAR",
    "num_uniforms",
    "p_is_rows",
    "p_is_rows_block",
    "mh_cdf_invert",
    "ragged_edge_cdf",
    "ragged_edge_cdf_update",
    "CDF_TILE",
    "cdf_search_levels",
    "edge_cdf_tiles",
    "ragged_mh_invert",
    "combine_bucketed",
    "bucket_capacities",
    "compact_plan",
    "scatter_compacted",
    "mhlj_transition_math",
    "combine_mh_jump",
    "levy_jump_batched",
    "WalkEngine",
    "WALK_TRANSITION_SCOPE",
    "EDGE_CDF_BUILD_SPAN",
    "span_event",
]

# Uniform-block slot layout (shared with the Pallas kernel).
U_JUMP, U_MH, U_DIST, U_HOP0 = 0, 1, 2, 3

# Registered row layouts of the pallas backend.  Anything listed here is
# exercised by the benchmark anti-rot tier (benchmarks/run.py --smoke), so a
# new layout cannot silently rot out of tier-1 coverage.
LAYOUTS = ("sparse", "dense", "bucketed", "ragged")

# Layouts whose Pallas kernel the TPU compiler (Mosaic) accepts, so where
# an explicit backend="pallas" runs compiled on a TPU.  The sparse, bucketed
# and dense kernels take a cumsum, which Mosaic does not lower.  "auto" runs
# the scan backend (XLA) on every layout: measured on a TPU v5e, the ragged
# kernel lost to the scan path's W-wide gathers in every chipbench cell
# (PERF_LEDGER.jsonl, PERF.md §6); see WalkEngine.resolved_backend.
TPU_PALLAS_LAYOUTS = ("ragged",)

# Environment override for backend="auto": set REPRO_BACKEND=scan|pallas to
# pin the resolved backend (off-TPU the pallas backend runs interpret mode).
# This is what the CI matrix flips to run tier-1 under both backends.
BACKEND_ENV_VAR = "REPRO_BACKEND"

# Names the program gives its layers.  The device scope covers one MHLJ
# transition of every layout and backend (``jax.named_scope``: HLO metadata
# only, so a profile can attribute device time to it).  The host span covers
# the flat CDF build of the ragged layout's set-up.
WALK_TRANSITION_SCOPE = "walk_transition"
EDGE_CDF_BUILD_SPAN = "edge_cdf_build"

# Width of the aligned tiles the ragged MH move searches (ragged_mh_invert):
# the TPU's 128 lanes, so one tile is one lane-aligned row of a (m, 128)
# table and each search level is one W-wide gather of whole rows.
CDF_TILE = 128


def span_event(span: str, counter: str = "seconds") -> str:
    """The ``jax.monitoring`` event of a host span or device scope:
    ``seconds`` carries a span's duration, any other counter is one event
    per occurrence inside it (for the ``walk_transition`` scope, one
    ``backend_<backend>`` event each time a transition is traced, and on
    the ragged layout's XLA path one ``mh_search_levels_<L>`` event)."""
    return f"/repro/{span}/{counter}"


# Seconds spent in each host span since the process started, for a reader
# that was not listening when the span ran (a benchmark's set-up metric).
span_seconds: dict = {}


@contextlib.contextmanager
def _host_span(name: str):
    """A profiler span (``jax.profiler.TraceAnnotation``) whose duration is
    also recorded through ``jax.monitoring`` and added to ``span_seconds``;
    with neither a profiler nor a listener it costs a clock read."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    seconds = time.perf_counter() - t0
    span_seconds[name] = span_seconds.get(name, 0.0) + seconds
    jax.monitoring.record_event_duration_secs(span_event(name), seconds)


def num_uniforms(r: int) -> int:
    """Columns of the pre-drawn uniform block for jump range ``r``."""
    return U_HOP0 + r


def p_is_rows(
    neighbors: jnp.ndarray,
    degrees: jnp.ndarray,
    lipschitz: jnp.ndarray,
    nodes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """P_IS rows of Eq. (7) over padded neighbor lists, from local info only.

    P(v,u) = min{1/deg(v), L_u / (deg(u) L_v)} for true neighbors u != v;
    leftover mass goes to the single true self slot, pads carry exactly 0
    (the shared padded-row convention of ``core.transition``, which keeps
    bucket-width row truncations bitwise-exact).

    ``nodes=None`` returns the full (n, max_deg) table (Pallas backend /
    precomputation); ``nodes=(W,)`` returns only those W rows (scan backend).
    """
    if nodes is None:
        nodes = jnp.arange(neighbors.shape[0], dtype=jnp.int32)
    return p_is_rows_block(
        neighbors[nodes], nodes, degrees[nodes], degrees, lipschitz
    )


def p_is_rows_block(
    nbrs: jnp.ndarray,  # (W, width) padded neighbor block
    self_ids: jnp.ndarray,  # (W,) owning node id per row
    deg_v: jnp.ndarray,  # (W,) true degree per row
    degrees: jnp.ndarray,  # (n,) full degree vector (neighbor lookups)
    lipschitz: jnp.ndarray,  # (n,)
) -> jnp.ndarray:
    """Eq.-7 rows on an arbitrary padded neighbor block — THE live-row math.

    Shared by the full-width path (:func:`p_is_rows`) and the per-bucket
    dispatch of ``layout="bucketed"``; ``width`` may be anything ≥ the
    rows' true degrees.  Pads carry exactly 0 and leftover mass lands on
    the self slot, mirroring ``transition._mh_rows_block``.
    """
    deg_vf = deg_v.astype(jnp.float32)[:, None]
    deg_u = degrees[nbrs].astype(jnp.float32)
    l_v = lipschitz[self_ids][:, None]
    l_u = lipschitz[nbrs]
    move = jnp.minimum(1.0 / deg_vf, l_u / (deg_u * l_v))
    is_pad = (
        jax.lax.broadcasted_iota(jnp.int32, nbrs.shape, 1)
        >= deg_v.astype(jnp.int32)[:, None]
    )
    is_self = (nbrs == self_ids[:, None]) & ~is_pad
    move = jnp.where(is_self | is_pad, 0.0, move)
    p_stay = 1.0 - move.sum(axis=-1, keepdims=True)
    probs = jnp.where(is_self, p_stay, move)
    return jnp.maximum(probs, 0.0)


def mh_cdf_invert(
    rows: jnp.ndarray,  # (W, width) padded probability rows
    neigh_rows: jnp.ndarray,  # (W, width) matching padded neighbor rows
    u_mh: jnp.ndarray,  # (W,) the U_MH uniform per walk
) -> jnp.ndarray:
    """THE MH-move CDF inversion over padded rows; returns ``v_mh`` (W,).

    Vectorized over any row width (``max_deg`` for the sparse/scan paths, a
    bucket width for the bucketed dispatch).  The Pallas tile kernel
    (``walk_transition_sparse``) and the dense kernel's per-walk body
    mirror this arithmetic statement for statement, and the parity tests
    assert bitwise-equal outputs.
    """
    width = rows.shape[1]
    cdf = jnp.cumsum(rows, axis=1)
    idx = jnp.sum(
        (cdf < u_mh[:, None] * cdf[:, -1:]).astype(jnp.int32), axis=1
    )
    idx = jnp.minimum(idx, width - 1)
    return jnp.take_along_axis(neigh_rows, idx[:, None], axis=1)[:, 0]


@_host_span(EDGE_CDF_BUILD_SPAN)
def ragged_edge_cdf(
    indptr,
    indices,
    degrees,
    *,
    row_probs=None,
    lipschitz=None,
    chunk_rows: Optional[int] = None,
    width: Optional[int] = None,
) -> jnp.ndarray:
    """THE flat per-edge CDF builder of the ragged layout — (nnz,) float32.

    Entry ``indptr[v] + k`` holds the inclusive CDF prefix of row v at
    slot k, bit-for-bit equal to ``jnp.cumsum(padded_row)[k]`` — the value
    :func:`mh_cdf_invert` compares against on the padded layouts.  That
    exactness is free, not assumed: rows are materialized in bounded-size
    chunks at the **full** ``max_deg`` width (the identical
    :func:`p_is_rows_block` / cumsum ops the other layouts run) and the
    pad columns — exact zeros that never move a CDF prefix — are then
    dropped by ``graphs.flat_edge_values``.  No O(n·max_deg) array ever
    exists; transient memory is O(chunk·max_deg) and the resident result
    is exactly O(E).

    Row source: ``row_probs`` as an (n, max_deg) padded table, a flat
    (nnz,) probability buffer (``transition.mh_importance_rows_ragged``
    et al.), or live Eq.-7 rows from a ``lipschitz`` vector.  Host-side
    only (chunking is a python loop) — the engine builds this once at
    construction, never per step.

    ``width`` pins the padded materialization width (default: the
    graph's ``max_deg``).  The bits of a row's CDF prefix **depend on
    that width**: XLA's CPU reductions lane-split by row length, so the
    same probabilities summed at width 29 vs 600 differ in the last
    ulp.  Incremental churn therefore rebuilds touched rows at the
    *engine's recorded build width* (``WalkEngine.cdf_width``), not the
    churned graph's possibly-different max degree — the only way copied
    untouched segments and freshly patched rows can share one bitwise
    story.  A ``width`` below the actual max degree raises.
    """
    from repro.core.graphs import (
        _pad_neighbor_lists,
        _ragged_row_chunks,
        flat_edge_values,
    )

    indptr_np = np.asarray(indptr, dtype=np.int64)
    indices_np = np.asarray(indices)
    deg_np = np.asarray(degrees, dtype=np.int64)
    n, nnz, max_deg = deg_np.size, indices_np.shape[0], int(deg_np.max())
    if width is None:
        width = max_deg
    elif width < max_deg:
        raise ValueError(
            f"width={width} cannot cover max degree {max_deg}; CDF rows "
            "must materialize at least as wide as the longest row"
        )
    flat_probs = None
    if row_probs is not None:
        rp = np.asarray(row_probs)
        if rp.ndim == 1:
            if rp.shape[0] != nnz:
                raise ValueError(
                    f"flat row_probs must have nnz={nnz} entries, got "
                    f"{rp.shape[0]}"
                )
            flat_probs = rp.astype(np.float32)
        elif rp.shape != (n, max_deg):
            raise ValueError(
                f"row_probs must be (n, max_deg)=({n}, {max_deg}) or flat "
                f"(nnz,), got {rp.shape}"
            )
    elif lipschitz is None:
        raise ValueError(
            "ragged_edge_cdf needs a row source: row_probs (padded table "
            "or flat buffer) or lipschitz"
        )
    if lipschitz is not None and row_probs is None:
        lips = jnp.asarray(lipschitz, jnp.float32)
        deg_j = jnp.asarray(deg_np, jnp.int32)
    out = np.empty(nnz, dtype=np.float32)
    cols = np.arange(width)
    for ids in _ragged_row_chunks(n, width, chunk_rows):
        jax.monitoring.record_event(span_event(EDGE_CDF_BUILD_SPAN, "chunks"))
        if flat_probs is not None:
            rows = np.zeros((ids.size, width), dtype=np.float32)
            mask = cols[None, :] < deg_np[ids][:, None]
            rows[mask] = flat_probs[
                indptr_np[ids[0]] : indptr_np[ids[-1] + 1]
            ]
            rows = jnp.asarray(rows)
        elif row_probs is not None:
            block = rp[ids]
            if block.shape[1] < width:
                block = np.pad(
                    block, ((0, 0), (0, width - block.shape[1]))
                )
            rows = jnp.asarray(block)
        else:
            nbrs = _pad_neighbor_lists(
                indptr_np, indices_np, deg_np, node_ids=ids, width=width
            )
            rows = p_is_rows_block(
                jnp.asarray(nbrs),
                jnp.asarray(ids, jnp.int32),
                deg_j[ids],
                deg_j,
                lips,
            )
        cdf = np.asarray(jnp.cumsum(rows, axis=1))
        out[indptr_np[ids[0]] : indptr_np[ids[-1] + 1]] = flat_edge_values(
            indptr_np, deg_np, cdf, node_ids=ids
        )
    return jnp.asarray(out)


def ragged_edge_cdf_update(
    old_indptr,
    old_degrees,
    old_edge_cdf,
    new_indptr,
    new_indices,
    new_degrees,
    touched_rows,
    *,
    touched_probs=None,
    lipschitz=None,
    width: Optional[int] = None,
) -> jnp.ndarray:
    """Incremental flat per-edge CDF after a batched edge churn — (nnz',).

    The segment-local counterpart of :func:`ragged_edge_cdf`: every row
    *not* in ``touched_rows`` keeps its old CDF segment **verbatim** (the
    per-row cumsum makes each segment bitwise-independent of every other
    row), and only the touched rows — ``graphs.EdgeChurn.touched_rows``:
    churn endpoints plus new-graph neighbors of degree-changed nodes — are
    recomputed, through the **identical** :func:`p_is_rows_block` /
    ``jnp.cumsum`` / ``flat_edge_values`` ops the from-scratch builder
    runs, at the **same materialization width**.  That last clause is
    load-bearing: XLA's CPU reductions lane-split by row width, so the
    same probabilities padded to a different width differ in the last
    ulp — a row's bits are a function of (values, width), not values
    alone.  Pass ``width`` = the width the *old* CDF was built at
    (``WalkEngine.cdf_width``); the result is then bitwise-identical to
    ``ragged_edge_cdf(new_graph, width=width)`` (the differential tests
    in ``tests/test_dynamic_graphs.py`` pin this on every layout) while
    the work is O(E) copies + O(touched·width) recompute instead of a
    full O(E log E) rebuild.  Default width: the new graph's max degree
    — only safe when churn did not change it.  A width below the new
    max degree raises: the caller must escalate to a full
    :func:`ragged_edge_cdf` rebuild at the wider width instead
    (``WalkEngine.apply_churn`` does).

    Row source for the touched rows: ``touched_probs`` — a flat float32
    buffer of length ``sum(new_degrees[touched_rows])`` in ascending-row
    CSR edge order, e.g. any ``transition.*_rows_ragged`` builder called
    with ``node_ids=touched_rows`` — or live Eq.-7 rows from a full-length
    ``lipschitz`` vector.  Exactly one must be given.

    Validation is strict: the node count must be unchanged (churn moves
    edges, never nodes), ``touched_rows`` must be unique ascending in
    range, and any row outside it whose degree changed raises — an
    incomplete touched set would silently corrupt the walk law otherwise.
    """
    from repro.core.graphs import (
        _concat_ranges,
        _pad_neighbor_lists,
        flat_edge_values,
    )

    old_indptr_np = np.asarray(old_indptr, dtype=np.int64)
    deg_old = np.asarray(old_degrees, dtype=np.int64)
    old_cdf = np.asarray(old_edge_cdf, dtype=np.float32)
    new_indptr_np = np.asarray(new_indptr, dtype=np.int64)
    indices_np = np.asarray(new_indices)
    deg_new = np.asarray(new_degrees, dtype=np.int64)
    touched = np.asarray(touched_rows, dtype=np.int64)
    n = deg_new.size
    if deg_old.size != n:
        raise ValueError(
            "node count changed across the churn; apply_edge_churn moves "
            "edges, never nodes"
        )
    if touched.size and (
        np.any(np.diff(touched) <= 0) or touched[0] < 0 or touched[-1] >= n
    ):
        raise ValueError(
            "touched_rows must be unique ascending node ids in range "
            "(EdgeChurn.touched_rows is)"
        )
    if (touched_probs is None) == (lipschitz is None):
        raise ValueError(
            "pass exactly one row source: touched_probs (flat buffer over "
            "the touched rows) or lipschitz (full vector, live Eq.-7 rows)"
        )
    keep = np.ones(n, dtype=bool)
    keep[touched] = False
    keep_ids = np.nonzero(keep)[0]
    if not np.array_equal(deg_old[keep_ids], deg_new[keep_ids]):
        raise ValueError(
            "a row outside touched_rows changed degree; touched_rows must "
            "cover every changed row (use EdgeChurn.touched_rows)"
        )
    out = np.empty(int(new_indptr_np[-1]), dtype=np.float32)
    out[_concat_ranges(new_indptr_np[keep_ids], deg_new[keep_ids])] = old_cdf[
        _concat_ranges(old_indptr_np[keep_ids], deg_old[keep_ids])
    ]
    max_deg = int(deg_new.max())
    if width is None:
        width = max_deg
    elif width < max_deg:
        raise ValueError(
            f"width={width} cannot cover the new max degree {max_deg}; "
            "the churn outgrew the old build width — escalate to a full "
            "ragged_edge_cdf rebuild at the wider width"
        )
    if touched.size == 0:
        return jnp.asarray(out)
    deg_t = deg_new[touched]
    if lipschitz is not None:
        deg_j = jnp.asarray(deg_new, jnp.int32)
        lips_j = jnp.asarray(lipschitz, jnp.float32)
        tp = tp_off = None
    else:
        tp = np.asarray(touched_probs, dtype=np.float32)
        expect = int(deg_t.sum())
        if tp.ndim != 1 or tp.shape[0] != expect:
            raise ValueError(
                f"touched_probs must be a flat ({expect},) buffer covering "
                f"the touched rows in CSR edge order, got {tp.shape}"
            )
        tp_off = np.concatenate([[0], np.cumsum(deg_t)])
    # bounded-memory recompute: the same ~32 MB transient-block rule as
    # the from-scratch builder (graphs._ragged_row_chunks), applied to
    # slices of the touched list — a hub-heavy closure at a large width
    # would otherwise materialize one (touched, width) block of hundreds
    # of MB and fall off the builder's cell throughput
    chunk = max(256, (32 << 20) // max(1, 4 * width))
    cols = np.arange(width)
    for a in range(0, touched.size, chunk):
        ids = touched[a : a + chunk]
        dt = deg_t[a : a + chunk]
        if lipschitz is not None:
            nbrs = _pad_neighbor_lists(
                new_indptr_np, indices_np, deg_new, node_ids=ids,
                width=width,
            )
            rows = p_is_rows_block(
                jnp.asarray(nbrs),
                jnp.asarray(ids, jnp.int32),
                deg_j[ids],
                deg_j,
                lips_j,
            )
        else:
            rows_np = np.zeros((ids.size, width), dtype=np.float32)
            rows_np[cols[None, :] < dt[:, None]] = tp[
                tp_off[a] : tp_off[a + ids.size]
            ]
            rows = jnp.asarray(rows_np)
        cdf = np.asarray(jnp.cumsum(rows, axis=1))
        out[_concat_ranges(new_indptr_np[ids], dt)] = flat_edge_values(
            new_indptr_np, deg_new, cdf, node_ids=ids
        )
    return jnp.asarray(out)


def cdf_search_levels(max_degree: int, tile: int = CDF_TILE) -> int:
    """Levels L of the tiled CDF search for rows of up to ``max_degree``
    entries: the least L >= 1 with ``max_degree <= (tile + 1) * tile **
    (L - 1)``, so that the blocks of ``tile ** (L - 1)`` entries one row
    touches always lie in two adjacent aligned tiles of the top level."""
    levels = 1
    while max_degree > (tile + 1) * tile ** (levels - 1):
        levels += 1
    return levels


@functools.partial(jax.jit, static_argnames=("max_degree", "tile"))
def edge_cdf_tiles(
    edge_cdf: jnp.ndarray, *, max_degree: int, tile: int = CDF_TILE
) -> Tuple[jnp.ndarray, ...]:
    """The tile tables of :func:`ragged_mh_invert`, derived from the flat
    per-edge CDF — one ``(m_l, tile)`` float32 array per search level.

    Level 0 is ``edge_cdf`` itself, padded to whole tiles plus one and
    viewed as rows of ``tile``; level ``l + 1`` holds the last entry of
    every level-``l`` tile (the CDF at the end of each aligned block of
    ``tile ** (l + 1)`` entries), padded and viewed the same way.  There
    are :func:`cdf_search_levels` levels.  Pad entries are never read as
    values: the search masks entries by position, not by value.
    """
    level = edge_cdf.astype(jnp.float32)
    tiles = []
    for _ in range(cdf_search_levels(max_degree, tile)):
        rows = -(-level.shape[0] // tile) + 1
        table = jnp.pad(level, (0, rows * tile - level.shape[0]))
        tiles.append(table.reshape(rows, tile))
        level = tiles[-1][:, -1]
    return tuple(tiles)


def ragged_mh_invert(
    indptr: jnp.ndarray,  # (n+1,) int32 CSR row pointers
    degrees: jnp.ndarray,  # (n,) int32
    indices: jnp.ndarray,  # (nnz,) int32 CSR neighbor ids
    edge_cdf: jnp.ndarray,  # (nnz,) float32 flat per-edge CDF
    cdf_tiles: Tuple[jnp.ndarray, ...],  # edge_cdf_tiles(edge_cdf, ...)
    nodes: jnp.ndarray,  # (W,) int32 current node per walk
    u_mh: jnp.ndarray,  # (W,) the U_MH uniform per walk
) -> jnp.ndarray:
    """THE ragged MH-move inversion: a lower-bound search of each walk's
    own CDF segment over aligned tiles; returns ``v_mh`` (W,).

    The padded layouts count ``cdf < u · cdf[-1]`` across the full row
    width; over a non-decreasing CDF that count is the lower bound of
    ``t = u · total`` in the row's true-degree segment
    ``edge_cdf[s : e]`` (``s = indptr[v]``, ``e = s + deg(v)``) — pad
    slots (trailing exact-total entries on the padded row, ``u < 1``
    strictly) never counted anyway.  The search finds that bound level by
    level in the tables of :func:`edge_cdf_tiles`: at the top level it
    reads the two adjacent aligned tiles that hold every block the row
    touches, at each level below the one tile inside the block found
    above, and counts the entries that end a block inside the row (masked
    by position, never by value) and are ``< t``.  That count is the
    first block holding an entry ``>= t``; at level 0 it is the entry.
    Each level is one W-wide gather of whole tiles (``(W, 2, tile)`` at
    the top, ``(W, tile)`` below) in place of ~log2(tile) dependent
    one-word rounds.  Given the flat CDF of :func:`ragged_edge_cdf` the
    returned neighbor is bitwise-equal to :func:`mh_cdf_invert` on the
    padded row per key.  This is the scan backend's ragged MH move, and
    through ``kernels.walk_transition.ref`` the oracle of the fused
    kernel (``walk_transition_ragged``), which binary-searches the flat
    CDF per walk to the same bound.
    """
    tile = cdf_tiles[0].shape[1]
    top = len(cdf_tiles) - 1
    start = indptr[nodes]
    deg = degrees[nodes]
    end = start + deg
    t = (u_mh * edge_cdf[end - 1])[:, None]

    def first_at_or_above(vals, block0, level):
        # vals: the ends of blocks block0 + k of tile ** level entries; the
        # block that holds the first entry >= t, counted from the first
        # of them that ends in-row
        size = tile**level
        lo, hi = start // size, end // size
        block = block0[:, None] + jnp.arange(vals.shape[1], dtype=block0.dtype)
        in_row = (block >= lo[:, None]) & (block < hi[:, None])
        below = jnp.sum((in_row & (vals < t)).astype(jnp.int32), axis=1)
        return jnp.maximum(lo, block0) + below

    pair = start // tile ** (top + 1)
    vals = cdf_tiles[top][pair[:, None] + jnp.arange(2, dtype=pair.dtype)]
    found = first_at_or_above(
        vals.reshape(vals.shape[0], 2 * tile), pair * tile, top
    )
    for level in range(top - 1, -1, -1):
        found = first_at_or_above(cdf_tiles[level][found], found * tile, level)
    idx = jnp.minimum(found - start, deg - 1)
    return indices[start + idx]


def combine_bucketed(
    bucket_ids: jnp.ndarray, results_by_bucket
) -> jnp.ndarray:
    """THE bucket-merge rule: walk w keeps result of bucket ``bucket_ids[w]``.

    Every per-bucket dispatcher (the engine's scan fallback, the Pallas
    ``walk_transition_bucketed`` and the ``ref`` oracle) routes through
    this, so the keep-own-bucket convention exists exactly once.
    """
    merged = None
    for b, vm in enumerate(results_by_bucket):
        merged = vm if merged is None else jnp.where(bucket_ids == b, vm, merged)
    return merged


def bucket_capacities(
    num_walks: int,
    shares: Tuple[float, ...],
    capacity_factor: float,
    *,
    min_cap: int = 32,
    lane: int = 8,
) -> Tuple[int, ...]:
    """Static per-bucket walk capacities for the compacted dispatch.

    THE capacity rule, documented once: bucket b gets
    ``min(W, round_up(max(min_cap, ceil(capacity_factor · W · share_b)),
    lane))`` lanes.  ``share_b`` is the bucket's expected walk share —
    the engine uses ``max(node share n_b/n, degree share E_b/E)``,
    because walk occupancy tracks node share under the MH-IS stationary
    law but is *degree*-biased through the Lévy branch (uniform hops land
    on a node with probability ∝ its degree) and the simple-RW MH
    proposal, so hub buckets hold far more walks than their node count
    suggests.  ``capacity_factor`` > 1 leaves headroom for per-step
    fluctuation, ``min_cap`` keeps near-empty hub buckets from
    overflowing on bursts, and ``lane`` rounding keeps tile shapes
    friendly.  Everything here is a python number known at trace time
    (shapes + graph construction constants), so the capacities are
    jit-compile-time constants.  A step whose per-bucket walk counts
    exceed these capacities falls back to the uncompacted full-W dispatch
    (see :meth:`WalkEngine.step`) — same law, same bits, just slower.
    """
    caps = []
    for share in shares:
        c = math.ceil(capacity_factor * num_walks * share)
        c = max(c, min_cap)
        c = -(-c // lane) * lane
        caps.append(min(c, num_walks))
    return tuple(caps)


def compact_plan(
    bucket_ids: jnp.ndarray, num_buckets: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort the W walks by bucket id — THE compaction pass.

    Returns ``(order, starts, counts)``: ``order`` is the stable argsort of
    ``bucket_ids`` (walks of bucket b occupy positions
    ``starts[b] : starts[b] + counts[b]`` of ``order``, in original walk
    order within the bucket), ``counts[b]`` the number of walks currently
    in bucket b.  All shapes are static; only the values are traced.
    """
    counts = jnp.zeros(num_buckets, jnp.int32).at[bucket_ids].add(1)
    order = jnp.argsort(bucket_ids, stable=True).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    return order, starts, counts


def scatter_compacted(
    num_walks: int,
    walk_idx_by_bucket,
    valid_by_bucket,
    results_by_bucket,
) -> jnp.ndarray:
    """THE compacted merge rule: scatter per-bucket results back to walk
    order.

    Bucket b's pass produced ``results_by_bucket[b][lane]`` for walk
    ``walk_idx_by_bucket[b][lane]``; lanes beyond the bucket's walk count
    (``valid_by_bucket[b][lane] == False``) are capacity slop whose results
    are dropped — their scatter index is pushed out of bounds and JAX's
    ``mode="drop"`` discards them.  Valid lanes partition the walk set
    (each walk is in exactly one bucket), so the scatters never collide.
    Shared by the engine's scan path, the Pallas compacted dispatch
    (``kernels.walk_transition.walk_transition_bucketed_compacted``) and
    the ``ref`` oracle, so the merge convention exists exactly once.
    """
    out = jnp.zeros(num_walks, dtype=results_by_bucket[0].dtype)
    for widx, valid, res in zip(
        walk_idx_by_bucket, valid_by_bucket, results_by_bucket
    ):
        idx = jnp.where(valid, widx, num_walks)  # invalid -> out of bounds
        out = out.at[idx].set(res, mode="drop")
    return out


def mhlj_transition_math(
    nodes: jnp.ndarray,  # (W,) int32 current node per walk
    rows: jnp.ndarray,  # (W, max_deg) P_IS row per walk (padded)
    neighbors: jnp.ndarray,  # (n, max_deg) int32, pads = self id
    degrees: jnp.ndarray,  # (n,) int32
    uniforms: jnp.ndarray,  # (W, 3 + r); slot U_JUMP is a {0,1} flag
    p_d: float,
    r: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One Algorithm-1 transition for W walks — the canonical math.

    The MH-IS move is a per-walk CDF inversion (vmapped); the Lévy branch
    is :func:`levy_jump_batched`, shared verbatim with the sparse Pallas
    path so the jump law exists exactly once in pure JAX.  The Pallas
    kernels mirror this arithmetic (same CDF inversion, same
    :func:`trunc_geom_icdf`, same hop-index formula), and the parity tests
    assert bitwise-equal outputs given the same uniforms.

    Returns ``(next_nodes, hops)``, both ``(W,)`` int32; ``hops`` is the
    Remark-1 physical transition count (1 for MH, d for a jump).
    """
    v_mh = mh_cdf_invert(rows, neighbors[nodes], uniforms[:, U_MH])
    v_jump, d = levy_jump_batched(nodes, uniforms, neighbors, degrees, p_d, r)
    return combine_mh_jump(v_mh, v_jump, d, uniforms)


def combine_mh_jump(
    v_mh: jnp.ndarray, v_jump: jnp.ndarray, d: jnp.ndarray, uniforms: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolve the J~Ber(p_J) branch per walk — THE jump/MH combine.

    Selects the jump or MH destination from the ``U_JUMP`` flag and
    produces the Remark-1 hop count (1 for MH, d for a jump).  Shared by
    every pure-JAX path (scan and sparse Pallas) so the branch convention
    exists exactly once; the dense Pallas kernel mirrors it per walk.
    """
    do_jump = uniforms[:, U_JUMP] > 0.5
    v_next = jnp.where(do_jump, v_jump, v_mh)
    hops = jnp.where(do_jump, d, jnp.int32(1))
    return v_next, hops


def levy_jump_batched(
    nodes: jnp.ndarray,  # (W,) int32
    uniforms: jnp.ndarray,  # (W, 3 + r)
    neighbors: Optional[jnp.ndarray],  # (n, max_deg) int32, or None with csr=
    degrees: jnp.ndarray,  # (n,) int32
    p_d: float,
    r: int,
    *,
    csr: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The Lévy branch of Algorithm 1 for W walks — THE jump implementation.

    d ~ TruncGeom(p_d, r) then d uniform hops, expressed as W-wide XLA
    gathers (no dense table, no per-walk scan).  Consumed by the scan
    backend (via :func:`mhlj_transition_math`), the sparse Pallas path and
    the bucketed path; the dense Pallas kernel mirrors this arithmetic per
    walk.  Returns ``(v_jump, d)``.

    The k-th neighbor of ``v`` comes from the padded table
    (``neighbors[v, k]``) or, when ``csr=(indptr, indices)`` is given, from
    the flat CSR arrays (``indices[indptr[v] + k]``).  Hop indices always
    satisfy ``k < deg(v)``, where both sources hold the identical value —
    so the bucketed layout (which never materializes the padded table)
    samples the same jump bit for bit.
    """
    d = trunc_geom_icdf(uniforms[:, U_DIST], p_d, r)

    def hop(i, v_cur):
        deg = degrees[v_cur]
        hop_idx = jnp.minimum(
            (uniforms[:, U_HOP0 + i] * deg.astype(jnp.float32)).astype(jnp.int32),
            deg - 1,
        )
        if csr is None:
            v_new = neighbors[v_cur, hop_idx]
        else:
            indptr, indices = csr
            v_new = indices[indptr[v_cur] + hop_idx]
        return jnp.where(i < d, v_new, v_cur)

    v_jump = jax.lax.fori_loop(0, r, hop, nodes)
    return v_jump, d


@dataclasses.dataclass(frozen=True, eq=False)
class WalkEngine:
    """Batched MHLJ sampler for W parallel walks with pluggable backends.

    Construct once (``from_graph``) and call :meth:`step` inside jitted
    training loops or :meth:`run` for whole trajectories.  All fields are
    device arrays or static python scalars, so instances may also be built
    inside a trace (the regression trainer does).  Engines are registered
    as pytrees (array fields are leaves; backend/layout/shape statics are
    aux data), so an engine may also be passed *through* a ``jax.jit``
    boundary as an argument — the trainer does exactly that, which is what
    lets every layout (padded or bucketed) ride the same jitted loop.
    """

    neighbors: Optional[jnp.ndarray]  # (n, max_deg) int32, pads = self id;
    #   None on the bucketed layout, which never materializes the table
    degrees: jnp.ndarray  # (n,) int32
    p_j: Union[float, jnp.ndarray] = 0.1  # default jump prob (overridable per call)
    p_d: float = 0.5
    r: int = 3
    row_probs: Optional[jnp.ndarray] = None  # (n, max_deg) precomputed P_IS
    backend: str = "auto"  # "auto" | "scan" | "pallas"
    layout: str = "sparse"  # engine.LAYOUTS — pallas-backend row handling
    block_w: int = 256
    interpret: Optional[bool] = None  # None = auto (interpret off-TPU)
    # -- bucketed-layout compaction knobs (static) --------------------------
    compact: bool = True  # sort walks by bucket, run tiles at capacity
    capacity_factor: float = 1.25  # headroom of the bucket_capacities rule
    bucket_share: Optional[Tuple[float, ...]] = None  # per-bucket expected
    #   walk share, max(node share, degree share); None = node share only
    # -- bucketed/ragged-layout state (None on the padded layouts) ----------
    indptr: Optional[jnp.ndarray] = None  # (n+1,) int32 CSR row pointers
    indices: Optional[jnp.ndarray] = None  # (nnz,) int32 CSR neighbor ids
    node_bucket: Optional[jnp.ndarray] = None  # (n,) int32 bucket id per node
    node_slot: Optional[jnp.ndarray] = None  # (n,) int32 row within bucket
    bucket_neighbors: Optional[Tuple[jnp.ndarray, ...]] = None  # (n_b, w_b)
    bucket_rows: Optional[Tuple[jnp.ndarray, ...]] = None  # (n_b, w_b) P_IS
    # -- ragged-layout state (the O(E) true-degree path) --------------------
    edge_cdf: Optional[jnp.ndarray] = None  # (nnz,) float32 flat per-edge CDF
    cdf_tiles: Optional[Tuple[jnp.ndarray, ...]] = None  # edge_cdf_tiles:
    #   the (m_l, CDF_TILE) tables the scan backend's MH move searches
    max_degree: Optional[int] = None  # static: longest row
    cdf_width: Optional[int] = None  # width edge_cdf was materialized at —
    #   XLA reduction bits depend on the padded row width, so incremental
    #   churn must keep patching at THIS width (>= max_degree) to stay
    #   bitwise vs a same-width rebuild; apply_churn escalates to a full
    #   recompute only when an insert pushes max degree past it
    # -- fleet sharding (static; see repro.walk_sgd.fleet) -------------------
    walker_sharding: Optional[object] = None  # jax NamedSharding for the W
    #   walker axis; None = single-device (no constraints emitted).  When
    #   set, step/run pin the per-walk uniform block and outputs to the
    #   walker mesh axis so GSPMD keeps the whole transition
    #   walker-parallel (graph state stays replicated per
    #   repro.sharding.rules.fleet_specs).
    # -- dynamic graphs (static; see docs/dynamic_graphs.md) -----------------
    graph_version: int = 0  # bumped by apply_churn — static aux, so jitted
    #   consumers retrace across graph versions (an nnz change forces a
    #   retrace anyway; the counter makes equal-nnz churns explicit too,
    #   and walk-continuity bookkeeping keys off it)

    @classmethod
    def from_graph(
        cls,
        graph,
        params,
        *,
        row_probs=None,
        lipschitz: Optional[jnp.ndarray] = None,
        backend: str = "auto",
        layout: Optional[str] = None,
        block_w: int = 256,
        interpret: Optional[bool] = None,
        bucket_factor: Optional[int] = None,
        compact: bool = True,
        capacity_factor: float = 1.25,
    ) -> "WalkEngine":
        """Engine from any ``core.graphs`` class + ``MHLJParams``.

        ``Graph`` and ``CSRGraph`` expose the same padded
        ``neighbors``/``degrees`` tensors, so large CSR graphs plug in with
        no dense adjacency ever materialized; a ``BucketedCSRGraph``
        selects ``layout="bucketed"`` automatically and a
        ``RaggedCSRGraph`` selects ``layout="ragged"`` (and any graph is
        converted when either layout is requested explicitly, with
        ``bucket_factor`` picking the bucketed width ladder).  ``compact``
        / ``capacity_factor`` tune the bucketed layout's per-step walk
        compaction (see :meth:`step`); they are inert on the other
        layouts.  Row source precedence: explicit ``row_probs`` (an
        (n, max_deg) table, a per-bucket tuple for the bucketed layout —
        a full table is column-truncated per bucket, which is
        bitwise-exact — or a flat (nnz,) buffer for the ragged layout,
        e.g. ``transition.mh_importance_rows_ragged``), else rows
        precomputed from a *static* ``lipschitz`` vector, else live rows
        from the ``lipschitz=`` argument of :meth:`step` / :meth:`run`
        (the ragged layout, whose row state is the flat CDF built once at
        construction, requires one of the first two).
        """
        is_bucketed = hasattr(graph, "buckets")
        is_bare_csr = hasattr(graph, "indptr") and not (
            is_bucketed or hasattr(graph, "neighbors")
        )
        if layout is None:
            layout = (
                "bucketed" if is_bucketed
                else "ragged" if is_bare_csr
                else "sparse"
            )
        if layout == "ragged":
            # true-degree layout: resident row state is the flat per-edge
            # CDF (exactly O(E)); no padded or bucketed table is built
            core = graph if hasattr(graph, "indptr") else graph.to_csr()
            degrees = jnp.asarray(core.degrees, jnp.int32)
            if row_probs is None and lipschitz is None:
                raise ValueError(
                    "layout='ragged' precomputes its flat per-edge CDF at "
                    "construction; pass row_probs (padded table or flat "
                    "buffer) or lipschitz to from_graph"
                )
            edge_cdf = ragged_edge_cdf(
                core.indptr, core.indices, core.degrees,
                row_probs=row_probs, lipschitz=lipschitz,
            )
            max_degree = int(np.asarray(core.degrees).max())
            return cls(
                neighbors=None,
                degrees=degrees,
                p_j=params.p_j,
                p_d=params.p_d,
                r=params.r,
                row_probs=None,
                backend=backend,
                layout="ragged",
                block_w=block_w,
                interpret=interpret,
                compact=compact,
                capacity_factor=capacity_factor,
                indptr=jnp.asarray(core.indptr, jnp.int32),
                indices=jnp.asarray(core.indices, jnp.int32),
                edge_cdf=edge_cdf,
                cdf_tiles=edge_cdf_tiles(edge_cdf, max_degree=max_degree),
                max_degree=max_degree,
                cdf_width=max_degree,
            )
        if layout == "bucketed":
            # bucket_factor=None keeps an already-bucketed graph's ladder
            # as-is; an explicit value re-buckets on mismatch.  Every
            # sparse class buckets straight off its CSR core, so a bare
            # RaggedCSRGraph never materializes the padded table here.
            if is_bucketed and bucket_factor is None:
                bg = graph
            else:
                base = (
                    graph if hasattr(graph, "to_bucketed") else graph.to_csr()
                )
                bg = base.to_bucketed(bucket_factor=bucket_factor or 2)
            degrees = jnp.asarray(bg.degrees)
            bucket_neighbors = tuple(
                jnp.asarray(b.neighbors) for b in bg.buckets
            )
            if row_probs is not None:
                if isinstance(row_probs, (tuple, list)):
                    bucket_rows = tuple(jnp.asarray(x) for x in row_probs)
                else:  # (n, max_deg) table: exact per-bucket truncation
                    table = jnp.asarray(row_probs)
                    bucket_rows = tuple(
                        table[jnp.asarray(b.node_ids)][:, : b.width]
                        for b in bg.buckets
                    )
            elif lipschitz is not None:
                lips = jnp.asarray(lipschitz, jnp.float32)
                bucket_rows = tuple(
                    p_is_rows_block(
                        jnp.asarray(b.neighbors),
                        jnp.asarray(b.node_ids),
                        degrees[jnp.asarray(b.node_ids)],
                        degrees,
                        lips,
                    )
                    for b in bg.buckets
                )
            else:
                bucket_rows = None
            # expected walk share per bucket (static): max of node share
            # (MH-IS stationary occupancy) and degree share (Lévy-jump /
            # simple-RW-proposal occupancy) — see bucket_capacities
            total_deg = int(bg.degrees.sum())
            bucket_share = tuple(
                max(
                    int(b.node_ids.size) / bg.n,
                    int(bg.degrees[b.node_ids].sum()) / total_deg,
                )
                for b in bg.buckets
            )
            return cls(
                neighbors=None,
                degrees=degrees,
                p_j=params.p_j,
                p_d=params.p_d,
                r=params.r,
                row_probs=None,
                backend=backend,
                layout="bucketed",
                block_w=block_w,
                interpret=interpret,
                compact=compact,
                capacity_factor=capacity_factor,
                bucket_share=bucket_share,
                indptr=jnp.asarray(bg.indptr, jnp.int32),
                indices=jnp.asarray(bg.indices, jnp.int32),
                node_bucket=jnp.asarray(bg.node_bucket),
                node_slot=jnp.asarray(bg.node_slot),
                bucket_neighbors=bucket_neighbors,
                bucket_rows=bucket_rows,
            )
        if is_bucketed or is_bare_csr:
            graph = graph.to_csr()  # padded layouts need the full tensors
        neighbors = jnp.asarray(graph.neighbors)
        degrees = jnp.asarray(graph.degrees)
        if row_probs is None and lipschitz is not None:
            row_probs = p_is_rows(
                neighbors, degrees, jnp.asarray(lipschitz, jnp.float32)
            )
        return cls(
            neighbors=neighbors,
            degrees=degrees,
            p_j=params.p_j,
            p_d=params.p_d,
            r=params.r,
            row_probs=None if row_probs is None else jnp.asarray(row_probs),
            backend=backend,
            layout=layout,
            block_w=block_w,
            interpret=interpret,
            compact=compact,
            capacity_factor=capacity_factor,
        )

    def __post_init__(self):
        if self.backend not in ("auto", "scan", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.layout not in LAYOUTS:
            raise ValueError(f"unknown layout {self.layout!r}")

    def apply_churn(
        self,
        graph,
        churn,
        *,
        lipschitz=None,
        touched_probs=None,
    ) -> "WalkEngine":
        """New engine over a churned graph, recomputing only touched rows.

        ``graph`` is the **post-churn** sparse graph and ``churn`` the
        :class:`repro.core.graphs.EdgeChurn` receipt, both straight from
        ``apply_edge_churn``.  Row state is refreshed by
        :func:`ragged_edge_cdf_update` (untouched CDF segments copied
        verbatim, ``churn.touched_rows`` recomputed from ``lipschitz`` or
        ``touched_probs`` — exactly one) **at the engine's recorded
        ``cdf_width``**, so the patched buffer stays bitwise-identical to
        a same-width from-scratch rebuild even when the churn *lowered*
        the max degree (XLA reduction bits depend on the padded row
        width — see :func:`ragged_edge_cdf`).  Only when an insert pushes
        the max degree **past** ``cdf_width`` does the update escalate to
        a full :func:`ragged_edge_cdf` recompute at the new width — rare
        under random churn (an insert must land on the current hub), and
        the escalation needs a *full* row source: ``lipschitz`` works as
        is, while a ``touched_probs`` buffer restricted to the touched
        rows cannot rebuild untouched rows and must be passed full-length
        (nnz,) instead.  ``graph_version`` is bumped by one and every
        other engine knob carries over.  Walk positions are *not*
        migrated here — that is the fleet's continuity rule
        (:func:`repro.walk_sgd.fleet.migrate_walk_nodes`), which keys off
        the new degree vector.

        Ragged layout only: the other layouts' row state (padded tables /
        per-bucket tiles) has no segment-local structure worth patching —
        rebuild those engines via :meth:`from_graph`.
        """
        if self.layout != "ragged":
            raise ValueError(
                "incremental churn updates exist on layout='ragged' only "
                "(the flat per-edge CDF is segment-local); rebuild other "
                "layouts via WalkEngine.from_graph"
            )
        if not hasattr(graph, "indptr"):
            raise TypeError(
                "apply_churn needs the post-churn CSRGraph/RaggedCSRGraph "
                f"(got {type(graph).__name__})"
            )
        new_max = int(np.asarray(graph.degrees).max())
        old_width = self.cdf_width if self.cdf_width is not None else (
            self.max_degree
        )
        if new_max <= old_width:
            new_cdf = ragged_edge_cdf_update(
                np.asarray(self.indptr, dtype=np.int64),
                np.asarray(self.degrees),
                self.edge_cdf,
                graph.indptr,
                graph.indices,
                graph.degrees,
                churn.touched_rows,
                touched_probs=touched_probs,
                lipschitz=lipschitz,
                width=old_width,
            )
            new_width = old_width
        else:
            # escalation: the churn outgrew the recorded build width, so
            # EVERY row's bits change (width-dependent reductions) — a
            # segment patch cannot help; rebuild the whole flat CDF once
            # at the new width and record it
            if (touched_probs is None) == (lipschitz is None):
                raise ValueError(
                    "pass exactly one row source: touched_probs or "
                    "lipschitz"
                )
            nnz = int(np.asarray(graph.indices).shape[0])
            if touched_probs is not None:
                tp = np.asarray(touched_probs, dtype=np.float32)
                if tp.ndim != 1 or tp.shape[0] != nnz:
                    raise ValueError(
                        f"churn raised the max degree past the engine's "
                        f"cdf_width ({old_width} -> {new_max}); the "
                        "escalated full rebuild needs a full-length "
                        f"({nnz},) row-probability buffer, not one "
                        "restricted to the touched rows — recompute "
                        f"without node_ids (got {tp.shape})"
                    )
                new_cdf = ragged_edge_cdf(
                    graph.indptr, graph.indices, graph.degrees,
                    row_probs=tp, width=new_max,
                )
            else:
                new_cdf = ragged_edge_cdf(
                    graph.indptr, graph.indices, graph.degrees,
                    lipschitz=lipschitz, width=new_max,
                )
            new_width = new_max
        return dataclasses.replace(
            self,
            degrees=jnp.asarray(graph.degrees, jnp.int32),
            indptr=jnp.asarray(graph.indptr, jnp.int32),
            indices=jnp.asarray(graph.indices, jnp.int32),
            edge_cdf=new_cdf,
            cdf_tiles=edge_cdf_tiles(new_cdf, max_degree=new_max),
            max_degree=new_max,
            cdf_width=new_width,
            graph_version=self.graph_version + 1,
        )

    # -- backend resolution -------------------------------------------------

    @property
    def resolved_backend(self) -> str:
        """THE backend rule.  ``"auto"`` (unless :data:`BACKEND_ENV_VAR`
        pins it) is ``"scan"`` (XLA) on every layout and device: on a TPU
        v5e the scan path's W-wide gathers beat the ragged scalar-core
        kernel in every chipbench cell (``PERF_LEDGER.jsonl``).  An explicit
        or pinned ``"pallas"`` on a TPU, on a layout whose kernel the TPU
        compiler rejects (not in :data:`TPU_PALLAS_LAYOUTS`), raises unless
        ``interpret`` was asked for: a chip never drops to interpret mode
        silently."""
        backend = self.backend
        if backend == "auto":
            env = os.environ.get(BACKEND_ENV_VAR, "").strip().lower()
            if env not in ("scan", "pallas"):
                return "scan"
            backend = env
        if (
            backend == "pallas"
            and jax.default_backend() == "tpu"
            and self.interpret is None
            and self.layout not in TPU_PALLAS_LAYOUTS
        ):
            raise ValueError(
                f"the {self.layout!r} layout's Pallas kernel does not compile "
                f"for TPU (Mosaic has no cumsum lowering); use "
                f"backend='scan' or 'auto', or layout='ragged'"
            )
        return backend

    @property
    def resolved_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    # -- P_IS row plumbing --------------------------------------------------

    def rows_table(self, lipschitz: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Full (n, max_deg) P_IS table (precomputed or live Eq.-7).

        Only the dense layout consumes this; the sparse layout touches
        :meth:`rows_for` exclusively, so an engine with live rows never
        builds the whole table.
        """
        if self.layout == "bucketed":
            raise ValueError(
                "the bucketed layout has no full-width row table; rows live "
                "per degree bucket (bucket_rows)"
            )
        if self.layout == "ragged":
            raise ValueError(
                "the ragged layout has no full-width row table; row state "
                "is the flat per-edge CDF (edge_cdf)"
            )
        if self.row_probs is not None:
            return self.row_probs
        if lipschitz is None:
            raise ValueError(
                "engine has no precomputed row_probs; pass lipschitz= for "
                "live Eq. (7) rows"
            )
        return p_is_rows(self.neighbors, self.degrees, lipschitz)

    def rows_for(
        self, nodes: jnp.ndarray, lipschitz: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """P_IS rows for the W active walk positions only."""
        if self.layout == "bucketed":
            raise ValueError(
                "the bucketed layout has no full-width rows; per-bucket "
                "tiles come from _bucket_tiles (bucket_rows / live Eq. 7)"
            )
        if self.layout == "ragged":
            raise ValueError(
                "the ragged layout has no full-width rows; the MH move "
                "searches the flat per-edge CDF (ragged_mh_invert)"
            )
        if self.row_probs is not None:
            return self.row_probs[nodes]
        if lipschitz is None:
            raise ValueError(
                "engine has no precomputed row_probs; pass lipschitz= for "
                "live Eq. (7) rows"
            )
        return p_is_rows(self.neighbors, self.degrees, lipschitz, nodes=nodes)

    def _bucket_tiles(
        self, nodes: jnp.ndarray, lipschitz: Optional[jnp.ndarray] = None
    ):
        """Per-bucket (P_IS rows, neighbor tiles) for the W active walks.

        For each degree bucket b the W walks gather a ``(W, width_b)`` tile
        from the bucket's storage; a walk outside bucket b is pointed at
        the bucket's row 0 — a harmless dummy whose result the caller
        discards via the per-walk bucket mask.  Returns
        ``(bucket_id, rows_by_bucket, tiles_by_bucket)``.
        """
        if self.bucket_rows is None and lipschitz is None:
            raise ValueError(
                "engine has no precomputed bucket rows; pass lipschitz= for "
                "live Eq. (7) rows"
            )
        bid = self.node_bucket[nodes]
        slot = self.node_slot[nodes]
        deg_v = self.degrees[nodes]
        rows_by_bucket, tiles_by_bucket = [], []
        for b, nbrs_b in enumerate(self.bucket_neighbors):
            local = jnp.where(bid == b, slot, 0)
            tiles = nbrs_b[local]  # (W, width_b)
            if self.bucket_rows is not None:
                rows = self.bucket_rows[b][local]
            else:
                # live Eq.-7 rows at bucket width; out-of-bucket lanes mix a
                # dummy tile with their own degree — finite garbage, masked
                # away by the caller
                rows = p_is_rows_block(
                    tiles, nodes, deg_v, self.degrees, lipschitz
                )
            rows_by_bucket.append(rows)
            tiles_by_bucket.append(tiles)
        return bid, tuple(rows_by_bucket), tuple(tiles_by_bucket)

    def _bucketed_mh_full(
        self,
        nodes: jnp.ndarray,
        u_mh: jnp.ndarray,
        lipschitz: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Uncompacted bucketed MH move: every bucket pass runs all W walks.

        The pre-compaction dispatch, kept as (a) the ``compact=False``
        path and (b) the jit-able fallback a capacity overflow selects via
        ``lax.cond`` — so an adversarial walk distribution degrades to the
        old per-step cost, never to a wrong answer.
        """
        bid, rows_by_bucket, tiles_by_bucket = self._bucket_tiles(
            nodes, lipschitz
        )
        if self.resolved_backend == "pallas":
            from repro.kernels.walk_transition.kernel import (
                walk_transition_bucketed,
            )

            return walk_transition_bucketed(
                bid,
                rows_by_bucket,
                tiles_by_bucket,
                u_mh,
                block_w=self.block_w,
                interpret=self.resolved_interpret,
            )
        # scan fallback: same per-bucket math, pure jnp
        return combine_bucketed(
            bid,
            [
                mh_cdf_invert(rows, tiles, u_mh)
                for rows, tiles in zip(rows_by_bucket, tiles_by_bucket)
            ],
        )

    def compacted_bucket_inputs(
        self,
        nodes: jnp.ndarray,
        u_mh: jnp.ndarray,
        caps: Tuple[int, ...],
        order: jnp.ndarray,
        starts: jnp.ndarray,
        counts: jnp.ndarray,
        lipschitz: Optional[jnp.ndarray] = None,
    ):
        """THE compacted gather convention: per-bucket ``[cap_b, …]`` inputs
        from a :func:`compact_plan`.

        For each bucket b, slices ``cap_b`` walk indices out of the sorted
        order (the order vector is padded so no ``dynamic_slice`` ever
        clamps — lane j is exactly sorted position ``starts[b] + j``),
        marks lanes beyond ``counts[b]`` invalid, and gathers the bucket's
        neighbor/P_IS tiles with capacity-slop lanes pointed at the
        bucket's row 0 (a harmless dummy :func:`scatter_compacted` drops).
        Returns ``(walk_idx, valid, rows, tiles, u_mh)`` — each a tuple
        with one entry per bucket.  Shared by :meth:`step`'s compacted
        branch and the kernel-vs-oracle parity tests, so the gather
        convention exists exactly once.
        """
        order_p = jnp.concatenate(
            [order, jnp.zeros((max(caps),), order.dtype)]
        )
        widx_by, valid_by, rows_by, tiles_by, u_by = [], [], [], [], []
        for b, cap in enumerate(caps):
            widx = jax.lax.dynamic_slice(order_p, (starts[b],), (cap,))
            valid = jnp.arange(cap, dtype=counts.dtype) < counts[b]
            nodes_b = nodes[widx]
            slot = jnp.where(valid, self.node_slot[nodes_b], 0)
            tiles = self.bucket_neighbors[b][slot]
            if self.bucket_rows is not None:
                rows = self.bucket_rows[b][slot]
            else:
                rows = p_is_rows_block(
                    tiles, nodes_b, self.degrees[nodes_b],
                    self.degrees, lipschitz,
                )
            widx_by.append(widx)
            valid_by.append(valid)
            rows_by.append(rows)
            tiles_by.append(tiles)
            u_by.append(u_mh[widx])
        return (
            tuple(widx_by), tuple(valid_by), tuple(rows_by),
            tuple(tiles_by), tuple(u_by),
        )

    def _bucketed_mh_compacted(
        self,
        nodes: jnp.ndarray,
        u_mh: jnp.ndarray,
        lipschitz: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Compacted bucketed MH move: each bucket pays only its own walks.

        One :func:`compact_plan` stable sort groups the W walk indices by
        bucket id; bucket b's pass then gathers a
        ``[cap_b, width_b]`` tile (``cap_b`` from the static
        :func:`bucket_capacities` rule) instead of ``[W, width_b]``, and
        :func:`scatter_compacted` puts results back in walk order.  Per-
        walk arithmetic is identical to the full dispatch — same tile row,
        same uniform, same CDF inversion — so outputs are bitwise-equal
        per key.  If any bucket's walk count exceeds its capacity this
        step, ``lax.cond`` selects :meth:`_bucketed_mh_full` instead (both
        branches have static shapes, so the whole step stays jit-able).

        Returns ``(v_mh, overflow)`` — the traced overflow flag is the
        compaction telemetry :meth:`step` surfaces through its aux output,
        so the static :func:`bucket_capacities` rule can be *audited*
        (observed overflow rate) instead of guessed.
        """
        if self.bucket_rows is None and lipschitz is None:
            raise ValueError(
                "engine has no precomputed bucket rows; pass lipschitz= for "
                "live Eq. (7) rows"
            )
        num_walks = nodes.shape[0]
        if self.bucket_share is not None:
            shares = self.bucket_share
        else:  # engines built without from_graph: node share only
            n = int(self.degrees.shape[0])
            shares = tuple(
                int(nb.shape[0]) / n for nb in self.bucket_neighbors
            )
        caps = bucket_capacities(num_walks, shares, self.capacity_factor)
        bid = self.node_bucket[nodes]
        order, starts, counts = compact_plan(bid, len(caps))
        overflow = jnp.any(counts > jnp.asarray(caps, counts.dtype))

        def compacted(_):
            widx_by, valid_by, rows_by, tiles_by, u_by = (
                self.compacted_bucket_inputs(
                    nodes, u_mh, caps, order, starts, counts, lipschitz
                )
            )
            if self.resolved_backend == "pallas":
                from repro.kernels.walk_transition.kernel import (
                    walk_transition_bucketed_compacted,
                )

                return walk_transition_bucketed_compacted(
                    rows_by, tiles_by, u_by, widx_by, valid_by, num_walks,
                    block_w=self.block_w,
                    interpret=self.resolved_interpret,
                )
            return scatter_compacted(
                num_walks, widx_by, valid_by,
                [
                    mh_cdf_invert(rows, tiles, u_b)
                    for rows, tiles, u_b in zip(rows_by, tiles_by, u_by)
                ],
            )

        def fallback(_):
            return self._bucketed_mh_full(nodes, u_mh, lipschitz)

        return jax.lax.cond(overflow, fallback, compacted, None), overflow

    # -- fleet sharding ------------------------------------------------------

    def with_walker_sharding(self, sharding) -> "WalkEngine":
        """Shard-aware engine: pin walker-axis intermediates/outputs of
        :meth:`step`/:meth:`run` to ``sharding`` (a ``NamedSharding`` for a
        1-D ``(W,)`` walker batch, e.g. from
        ``repro.sharding.rules.resolve_walker_axis``).  The constraint is
        value-preserving — sharded results stay bitwise-identical per key
        to the single-device engine (``tests/test_fleet.py``)."""
        return dataclasses.replace(self, walker_sharding=sharding)

    def _constrain_walkers(self, x: jnp.ndarray) -> jnp.ndarray:
        """Pin dim 0 of ``x`` to the walker mesh axis (no-op when unset)."""
        s = self.walker_sharding
        if s is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(
            *(tuple(s.spec) + (None,) * x.ndim)[: x.ndim]
        )
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(s.mesh, spec)
        )

    def _per_walker_shard(self, kernel):
        """Run ``kernel(nodes, indptr, indices, edge_cdf, u)`` once per
        device on that device's walkers (``shard_map`` over the walker
        mesh axis; graph state replicated).  The TPU compiler does not
        partition a Pallas kernel call by itself; it refuses one."""
        from jax.sharding import PartitionSpec

        s = self.walker_sharding
        walk, repl = PartitionSpec(*s.spec[:1]), PartitionSpec()
        return jax.shard_map(
            kernel,
            mesh=s.mesh,
            in_specs=(walk, repl, repl, repl, walk),
            out_specs=(walk, walk),
            check_vma=False,
        )

    # -- the transition -----------------------------------------------------

    def step(
        self,
        key: jax.Array,
        nodes: jnp.ndarray,
        *,
        p_j: Optional[Union[float, jnp.ndarray]] = None,
        lipschitz: Optional[jnp.ndarray] = None,
        with_aux: bool = False,
        faults: Optional[tuple] = None,
    ):
        """One batched MHLJ transition.

        Args:
          key: PRNG key (consumed wholly by this step).
          nodes: (W,) int32 current positions, or a scalar for one walk.
          p_j: jump probability override (python float or traced scalar);
            defaults to the engine's ``p_j``.
          lipschitz: (n,) live Lipschitz vector when the engine has no
            precomputed rows.
          with_aux: also return step telemetry — currently
            ``{"compact_overflow": bool scalar}``, True when this step's
            compacted bucketed dispatch overflowed a static capacity and
            ``lax.cond`` took the full-W fallback (always False on the
            other layouts / with compaction off).  This is how the static
            :func:`bucket_capacities` rule is audited in production
            sweeps instead of guessed.
          faults: optional ``(FaultModel, FaultState)`` pair — the
            liveness-masked transition path (docs/faults.md).  The
            backend proposal is computed exactly as without faults (scan
            and Pallas stay bitwise-identical per key), then
            :func:`repro.core.faults.apply_liveness` rejects handoffs
            onto dead nodes/edges like MH rejections and force-jumps
            walkers blocked past the model's ``patience`` to a uniform
            live node.  Requires ``with_aux=True``; the aux dict gains
            ``blocked_steps`` (the updated (W,) consecutive counter — the
            caller's next ``FaultState.blocked``), plus ``fault_blocked``
            and ``rescued`` (W,) masks.  ``faults=None`` consumes the key
            identically to the pre-fault engine (bitwise).

        Returns:
          (next_nodes, hops) matching the shape of ``nodes``; with
          ``with_aux``, (next_nodes, hops, aux).
        """
        if faults is not None and not with_aux:
            raise ValueError(
                "the liveness-masked path returns its blocked counter "
                "through aux; call step(..., faults=..., with_aux=True)"
            )
        if faults is not None:
            # split BEFORE the uniform draw so the rescue stream is
            # independent of the transition stream; the faults=None path
            # consumes the caller's key untouched (bitwise).
            key, rescue_key = jax.random.split(key)
        nodes = jnp.asarray(nodes, jnp.int32)
        squeeze = nodes.ndim == 0
        if squeeze:
            nodes = nodes[None]
        with jax.named_scope(WALK_TRANSITION_SCOPE):
            nxt, hops, overflow = self._transition(
                key, nodes, p_j, lipschitz, squeeze
            )
        aux = {"compact_overflow": overflow}
        if faults is not None:
            # liveness masking applies AFTER the backend dispatch, on the
            # proposed endpoints — every backend/layout pair shares this
            # exact rejection + rescue arithmetic (see docs/faults.md)
            fmodel, fstate = faults
            nxt, hops, blocked, was_blocked, rescued = faults_mod.apply_liveness(
                rescue_key,
                nodes,
                nxt,
                hops,
                jnp.atleast_1d(fstate.blocked),
                fmodel.live_mask(fstate),
                patience=fmodel.patience,
                rescue=fmodel.rescue,
                rescue_hops=self.r,
                edge_live=fmodel.edge_live_mask(fstate),
                indptr=self.indptr,
                indices=self.indices,
                max_degree=self.max_degree,
            )
            aux["blocked_steps"] = blocked[0] if squeeze else blocked
            aux["fault_blocked"] = was_blocked[0] if squeeze else was_blocked
            aux["rescued"] = rescued[0] if squeeze else rescued
        if self.walker_sharding is not None and not squeeze:
            nxt = self._constrain_walkers(nxt)
            hops = self._constrain_walkers(hops)
        if squeeze:
            nxt, hops = nxt[0], hops[0]
        if with_aux:
            return nxt, hops, aux
        return nxt, hops

    def _transition(self, key, nodes, p_j, lipschitz, squeeze):
        """The proposal of :meth:`step`: the uniform draw, the layout's
        backend dispatch and the combine, for (W,) ``nodes``.  Returns
        ``(next_nodes, hops, compact_overflow)``.  Records the resolved
        backend as one ``jax.monitoring`` event per trace
        (``/repro/walk_transition/backend_<backend>``), and on the ragged
        layout's XLA path the levels of its tiled CDF search
        (``/repro/walk_transition/mh_search_levels_<L>``)."""
        jax.monitoring.record_event(
            span_event(WALK_TRANSITION_SCOPE, "backend_" + self.resolved_backend)
        )
        p_j_t = self.p_j if p_j is None else p_j
        u = jax.random.uniform(
            key, (nodes.shape[0], num_uniforms(self.r)), jnp.float32
        )
        flag = (u[:, U_JUMP] < p_j_t).astype(jnp.float32)
        u = u.at[:, U_JUMP].set(flag)
        if self.walker_sharding is not None and not squeeze:
            u = self._constrain_walkers(u)
        overflow = jnp.asarray(False)

        if self.layout == "ragged":
            # true-degree path: the MH move searches the flat per-edge
            # CDF; resident row state is O(E).  No bucket ladder, no
            # compaction sort/scatter, no overflow cond.
            if self.edge_cdf is None:
                raise ValueError(
                    "ragged engine has no flat per-edge CDF; build it via "
                    "from_graph (row_probs or lipschitz)"
                )
            if self.resolved_backend == "pallas":
                # one fused scalar-prefetch kernel pass per walk tile:
                # inversion + r-hop Lévy gather + jump/MH combine, no
                # engine-side XLA gather round-trips
                from repro.kernels.walk_transition.kernel import (
                    walk_transition_ragged,
                )

                kernel = functools.partial(
                    walk_transition_ragged,
                    p_d=self.p_d,
                    r=self.r,
                    block_w=self.block_w,
                    interpret=self.resolved_interpret,
                )
                if self.walker_sharding is not None and not squeeze:
                    kernel = self._per_walker_shard(kernel)
                nxt, hops = kernel(
                    nodes, self.indptr, self.indices, self.edge_cdf, u
                )
            else:
                if self.cdf_tiles is None:
                    raise ValueError(
                        "ragged engine has no CDF tile tables; build it "
                        "via from_graph, or pass cdf_tiles=edge_cdf_tiles("
                        "edge_cdf, max_degree=...)"
                    )
                jax.monitoring.record_event(span_event(
                    WALK_TRANSITION_SCOPE,
                    f"mh_search_levels_{len(self.cdf_tiles)}",
                ))
                v_mh = ragged_mh_invert(
                    self.indptr, self.degrees, self.indices, self.edge_cdf,
                    self.cdf_tiles, nodes, u[:, U_MH],
                )
                v_jump, d = levy_jump_batched(
                    nodes, u, None, self.degrees, self.p_d, self.r,
                    csr=(self.indptr, self.indices),
                )
                nxt, hops = combine_mh_jump(v_mh, v_jump, d, u)
        elif self.layout == "bucketed":
            # per-bucket MH dispatch + CSR-gathered Lévy hops: resident
            # state is O(E + Σ_b n_b·width_b); no (n, max_deg) table exists.
            # With compaction on (and >1 bucket to dispatch), walks are
            # sorted by bucket id and each bucket's tile pass runs at its
            # static capacity instead of all W lanes; a capacity overflow
            # falls back to the full-W dispatch for that step.
            if self.compact and len(self.bucket_neighbors) > 1:
                v_mh, overflow = self._bucketed_mh_compacted(
                    nodes, u[:, U_MH], lipschitz
                )
            else:
                v_mh = self._bucketed_mh_full(nodes, u[:, U_MH], lipschitz)
            v_jump, d = levy_jump_batched(
                nodes, u, None, self.degrees, self.p_d, self.r,
                csr=(self.indptr, self.indices),
            )
            nxt, hops = combine_mh_jump(v_mh, v_jump, d, u)
        elif self.resolved_backend == "pallas" and self.layout == "dense":
            # local import: kernels package imports back into this module
            from repro.kernels.walk_transition.kernel import walk_transition

            nxt, hops = walk_transition(
                nodes,
                self.rows_table(lipschitz),
                self.neighbors,
                self.degrees,
                u,
                p_d=self.p_d,
                r=self.r,
                block_w=self.block_w,
                interpret=self.resolved_interpret,
            )
        elif self.resolved_backend == "pallas":
            # sparse layout: gather only the W active rows/neighbor tiles —
            # O(W·max_deg) working set, never the (n, max_deg) table
            from repro.kernels.walk_transition.kernel import (
                walk_transition_sparse,
            )

            v_mh = walk_transition_sparse(
                self.rows_for(nodes, lipschitz),
                self.neighbors[nodes],
                u[:, U_MH],
                block_w=self.block_w,
                interpret=self.resolved_interpret,
            )
            v_jump, d = levy_jump_batched(
                nodes, u, self.neighbors, self.degrees, self.p_d, self.r
            )
            nxt, hops = combine_mh_jump(v_mh, v_jump, d, u)
        else:
            nxt, hops = mhlj_transition_math(
                nodes,
                self.rows_for(nodes, lipschitz),
                self.neighbors,
                self.degrees,
                u,
                self.p_d,
                self.r,
            )
        return nxt, hops, overflow

    def run(
        self,
        key: jax.Array,
        v0s: jnp.ndarray,
        num_steps: int,
        *,
        p_j: Optional[Union[float, jnp.ndarray]] = None,
        lipschitz: Optional[jnp.ndarray] = None,
        with_aux: bool = False,
    ):
        """Whole trajectories for W walks (Algorithm 1's update sequence).

        ``p_j`` may be a scalar or a (num_steps,) schedule (Fig 6 annealing).

        Returns:
          update_nodes: (W, num_steps) int32 — element t is the node holding
            the model when update t runs (the first update runs at v0).
          hops: (W, num_steps) int32 — Remark-1 physical transitions taken
            after update t.
          Scalar ``v0s`` drops the leading walk axis.  With ``with_aux``, a
          third element carries per-step telemetry:
          ``{"compact_overflow": (num_steps,) bool}`` — which steps of the
          compacted bucketed dispatch overflowed their static capacities
          (``benchmarks/large_graph_walk.py`` records the rate so the
          ``capacity_factor`` rule is audited, not guessed).
        """
        v0s = jnp.asarray(v0s, jnp.int32)
        squeeze = v0s.ndim == 0
        if squeeze:
            v0s = v0s[None]
        p_j_base = self.p_j if p_j is None else p_j
        p_j_sched = jnp.broadcast_to(
            jnp.asarray(p_j_base, jnp.float32), (num_steps,)
        )
        keys = jax.random.split(key, num_steps)

        def body(v, xs):
            k, pj = xs
            v_next, hops, aux = self.step(
                k, v, p_j=pj, lipschitz=lipschitz, with_aux=True
            )
            return v_next, (v, hops, aux["compact_overflow"])

        _, (update_nodes, hops, overflow) = jax.lax.scan(
            body, v0s, (keys, p_j_sched)
        )
        update_nodes = update_nodes.T  # (T, W) -> (W, T)
        hops = hops.T
        if squeeze:
            update_nodes, hops = update_nodes[0], hops[0]
        if with_aux:
            return update_nodes, hops, {"compact_overflow": overflow}
        return update_nodes, hops


# -- pytree registration ----------------------------------------------------
#
# Array state (any layout's tensors, plus the possibly-traced p_j) flattens
# to leaves; compile-time knobs ride as hashable aux data.  This lets an
# engine cross a jit boundary as a plain argument — walk_sgd.trainer passes
# one engine object into its scanned training loop, so padded and bucketed
# layouts share the identical jitted code path.

_ENGINE_DATA_FIELDS = (
    "neighbors", "degrees", "p_j", "row_probs",
    "indptr", "indices", "node_bucket", "node_slot",
    "bucket_neighbors", "bucket_rows", "edge_cdf", "cdf_tiles",
)
_ENGINE_META_FIELDS = (
    "p_d", "r", "backend", "layout", "block_w", "interpret",
    "compact", "capacity_factor", "bucket_share", "max_degree",
    "cdf_width",
    "walker_sharding",  # NamedSharding is hashable -> valid static aux
    "graph_version",
)


def _engine_flatten(e: WalkEngine):
    children = tuple(getattr(e, f) for f in _ENGINE_DATA_FIELDS)
    aux = tuple(getattr(e, f) for f in _ENGINE_META_FIELDS)
    return children, aux


def _engine_unflatten(aux, children) -> WalkEngine:
    return WalkEngine(
        **dict(zip(_ENGINE_DATA_FIELDS, children)),
        **dict(zip(_ENGINE_META_FIELDS, aux)),
    )


jax.tree_util.register_pytree_node(
    WalkEngine, _engine_flatten, _engine_unflatten
)
