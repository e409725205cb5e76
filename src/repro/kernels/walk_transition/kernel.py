"""Batched MHLJ transition Pallas TPU kernels — the paper's orchestration hot
spot at scale (W parallel walks on a large silo graph, sampled every step).
These back the ``"pallas"`` backend of :class:`repro.core.engine.WalkEngine`;
the per-walk bodies mirror ``engine.mhlj_transition_math`` statement for
statement, and the parity tests assert bitwise-equal outputs.

Four entry points:

* :func:`walk_transition` — the ``layout="dense"`` path: the full
  ``(n, max_deg)`` P_IS/neighbor tables live in VMEM and every per-walk row
  is a dynamic-slice load.  Exact but caps n at a few thousand (VMEM).
* :func:`walk_transition_sparse` — the ``layout="sparse"`` MH-move kernel:
  the engine gathers only the W active rows into ``[block_w, max_deg]``
  tiles (an O(W·max_deg) working set, independent of n), the kernel runs a
  fully vectorized CDF inversion per tile, and the Lévy hop chain is left
  to the engine's O(W) XLA gathers.  This is what lets 100k-node graphs run
  with O(E) memory — no full table ever reaches kernel memory.
* :func:`walk_transition_bucketed` — the ``layout="bucketed"`` MH-move
  dispatch: one :func:`walk_transition_sparse` launch per degree bucket at
  that bucket's width (tiles ``[block_w, width_b]`` with width_b = 8, 16,
  …), each walk keeping the result of its own bucket's pass.  Hub rows
  only pay their own bucket's width, so hub-heavy graphs stop paying
  O(max_deg) per low-degree walk; the CDF inversion itself still exists
  exactly once (``_sparse_kernel``).
* :func:`walk_transition_ragged` — the ``layout="ragged"`` fused kernel:
  a ``PrefetchScalarGridSpec`` launch that runs on the TPU's scalar core.
  CSR ``indptr`` is scalar-prefetched into SMEM, each walk tile's nodes and
  uniforms ride in SMEM blocks, and the **flat** per-edge CDF/index
  buffers sit in VMEM, read one element at a time at each row's *true*
  degree — no padded tile is ever gathered, no bucket ladder dispatched.
  The whole MHLJ step fuses into the one pass: the MH move is a binary
  search of the walk's CDF segment (mirroring ``engine.ragged_mh_invert``),
  the Lévy branch runs its CSR-gathered hops in-kernel, and each walk
  writes ``(next, hops)`` of its own branch directly.

The dense kernel processes ``block_w`` walks per grid step.  Per walk:
  * MH-IS move: CDF inversion over the walk's padded P_IS neighbor row
    (precomputed or live (n, max_deg) table, resident in VMEM — graphs here
    are orchestration-scale, n <= a few thousand silos);
  * Lévy jump: distance d <- TruncGeom(p_d, r) via the shared closed-form
    inverse CDF (``core.levy.trunc_geom_icdf``), then d uniform hops using
    the neighbors/degrees tables.

What the TPU compiler accepts (compiled for a described v5e; see
``tests/test_tpu_compile.py``): only the ragged kernel.  The dense and
sparse kernels (and the bucketed dispatch, which reuses the sparse one)
take a ``cumsum``, which Mosaic does not lower, so they run in interpret
mode off-TPU only.  What a TPU runs under ``backend="auto"`` is the scan
backend (XLA) on every layout, the ragged one included: measured on a TPU
v5e, the ragged layout's W-wide XLA gathers beat this one-walk-at-a-time
scalar-core kernel in every chipbench cell (``PERF_LEDGER.jsonl``,
``PERF.md`` §6).  The ragged kernel runs compiled on a TPU only when
``backend="pallas"`` asks for it; ``engine.TPU_PALLAS_LAYOUTS`` and
``WalkEngine.resolved_backend`` hold that rule.

When W is not a multiple of ``block_w`` the walk axis is padded up to the
next block multiple and the padded lanes sliced off afterwards, so large
non-power-of-two fleets keep the intended grid instead of collapsing into
one giant block.

Inputs:
  nodes      (W,)  int32     current node per walk
  row_probs  (n, max_deg)    P_IS rows aligned with ``neighbors``
  neighbors  (n, max_deg)    int32 padded (pad = self id)
  degrees    (n, 1) int32
  uniforms   (W, 3 + r)      pre-drawn U(0,1) with slot layout
                             [jump_flag, mh, distance, hop_1..hop_r];
                             slot 0 arrives as a {0.0, 1.0} Bernoulli(p_J)
                             flag resolved by the engine (this is what lets
                             p_J be a traced annealing schedule while the
                             kernel keeps only static compile-time params)
Outputs:
  next_nodes (W,) int32
  hops       (W,) int32      Remark-1 physical transitions (1 MH, d jump)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine import (
    U_DIST,
    U_HOP0,
    U_JUMP,
    U_MH,
    combine_bucketed,
    num_uniforms,
    scatter_compacted,
)
from repro.core.levy import trunc_geom_icdf

__all__ = [
    "walk_transition",
    "walk_transition_sparse",
    "walk_transition_bucketed",
    "walk_transition_bucketed_compacted",
    "walk_transition_ragged",
]

_LANES = 128  # lanes of one VMEM vector row
_SMEM_TILE = 1024  # TPU tiling of a 1-D 32-bit array: compiled 1-D blocks
#   are a multiple of this, or span the whole array


def _kernel(
    nodes_ref, probs_ref, neigh_ref, deg_ref, u_ref, out_ref, hops_ref,
    *, p_d: float, r: int, block_w: int, max_deg: int,
):
    def one_walk(w, _):
        v = nodes_ref[w]

        # --- MH-IS move via CDF inversion over the padded neighbor row ----
        prow = probs_ref[pl.dslice(v, 1), :][0]  # (max_deg,)
        cdf = jnp.cumsum(prow)
        idx = jnp.sum((cdf < u_ref[w, U_MH] * cdf[-1]).astype(jnp.int32))
        idx = jnp.minimum(idx, max_deg - 1)
        nrow = neigh_ref[pl.dslice(v, 1), :][0]
        v_mh = jnp.take(nrow, idx, axis=0)

        # --- Lévy jump: shared TruncGeom inverse CDF, then d uniform hops -
        d = trunc_geom_icdf(u_ref[w, U_DIST], p_d, r)

        def hop(i, v_cur):
            deg = deg_ref[pl.dslice(v_cur, 1), :][0, 0]
            hop_idx = jnp.minimum(
                (u_ref[w, U_HOP0 + i] * deg.astype(jnp.float32)).astype(jnp.int32),
                deg - 1,
            )
            row = neigh_ref[pl.dslice(v_cur, 1), :][0]
            v_new = jnp.take(row, hop_idx, axis=0)
            return jnp.where(i < d, v_new, v_cur)

        v_jump = jax.lax.fori_loop(0, r, hop, v)

        do_jump = u_ref[w, U_JUMP] > 0.5
        out_ref[w] = jnp.where(do_jump, v_jump, v_mh)
        hops_ref[w] = jnp.where(do_jump, d, jnp.int32(1))
        return _

    jax.lax.fori_loop(0, block_w, one_walk, 0)


@functools.partial(
    jax.jit, static_argnames=("p_d", "r", "block_w", "interpret")
)
def walk_transition(
    nodes: jnp.ndarray,  # (W,) int32
    row_probs: jnp.ndarray,  # (n, max_deg) float32
    neighbors: jnp.ndarray,  # (n, max_deg) int32
    degrees: jnp.ndarray,  # (n,) int32
    uniforms: jnp.ndarray,  # (W, 3 + r) float32, slot 0 = jump flag
    *,
    p_d: float,
    r: int,
    block_w: int = 256,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    w = nodes.shape[0]
    n, max_deg = neighbors.shape
    n_u = num_uniforms(r)
    bw = min(block_w, w)
    # pad W up to a block multiple (padded lanes run a harmless MH move on
    # node 0 and are sliced off below)
    w_pad = -(-w // bw) * bw
    if w_pad != w:
        nodes = jnp.pad(nodes, (0, w_pad - w))
        uniforms = jnp.pad(uniforms, ((0, w_pad - w), (0, 0)))
    grid = (w_pad // bw,)

    def table(i):
        return (0, 0)

    next_nodes, hops = pl.pallas_call(
        functools.partial(
            _kernel, p_d=p_d, r=r, block_w=bw, max_deg=max_deg
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((n, max_deg), table),
            pl.BlockSpec((n, max_deg), table),
            pl.BlockSpec((n, 1), table),
            pl.BlockSpec((bw, n_u), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bw,), lambda i: (i,)),
            pl.BlockSpec((bw,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_pad,), jnp.int32),
            jax.ShapeDtypeStruct((w_pad,), jnp.int32),
        ],
        interpret=interpret,
    )(nodes, row_probs, neighbors, degrees[:, None], uniforms)
    return next_nodes[:w], hops[:w]


# ---------------------------------------------------------------------------
# Sparse-layout MH-move kernel (pre-gathered neighbor tiles)
# ---------------------------------------------------------------------------


def _sparse_kernel(probs_ref, neigh_ref, u_ref, out_ref, *, block_w, max_deg):
    """CDF inversion over a [block_w, max_deg] tile, fully vectorized.

    Same arithmetic as the per-walk body of ``mhlj_transition_math``
    (cumsum, ``cdf < u * cdf[-1]`` count, clamp) so outputs stay bitwise
    equal to the scan backend; the neighbor pick is a one-hot reduction
    instead of a gather.  The TPU compiler rejects the ``cumsum``, so this
    kernel runs in interpret mode only (see the module docstring).
    """
    prow = probs_ref[...]  # (block_w, max_deg) f32
    cdf = jnp.cumsum(prow, axis=1)
    u = u_ref[...]  # (block_w, 1) f32
    idx = jnp.sum((cdf < u * cdf[:, max_deg - 1][:, None]).astype(jnp.int32), axis=1)
    idx = jnp.minimum(idx, max_deg - 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_w, max_deg), 1)
    picked = jnp.where(cols == idx[:, None], neigh_ref[...], 0)
    out_ref[...] = jnp.sum(picked, axis=1)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def walk_transition_sparse(
    rows: jnp.ndarray,  # (W, max_deg) float32 — P_IS rows of the W walks
    neigh_rows: jnp.ndarray,  # (W, max_deg) int32 — their padded neighbor rows
    u_mh: jnp.ndarray,  # (W,) float32 — the U_MH uniform per walk
    *,
    block_w: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """MH-IS move for W walks from gathered [block_w, max_deg] tiles.

    Returns ``v_mh`` (W,) int32.  ``max_deg`` need not be a multiple of any
    block size — each tile spans the full (possibly odd) neighbor axis.
    The Lévy branch is composed outside (``engine.levy_jump_batched``).
    """
    w, max_deg = rows.shape
    bw = min(block_w, w)
    w_pad = -(-w // bw) * bw
    if w_pad != w:
        # padded lanes: all-zero rows -> idx 0 -> neighbor 0, sliced off below
        rows = jnp.pad(rows, ((0, w_pad - w), (0, 0)))
        neigh_rows = jnp.pad(neigh_rows, ((0, w_pad - w), (0, 0)))
        u_mh = jnp.pad(u_mh, (0, w_pad - w))
    v_mh = pl.pallas_call(
        functools.partial(_sparse_kernel, block_w=bw, max_deg=max_deg),
        grid=(w_pad // bw,),
        in_specs=[
            pl.BlockSpec((bw, max_deg), lambda i: (i, 0)),
            pl.BlockSpec((bw, max_deg), lambda i: (i, 0)),
            pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bw,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((w_pad,), jnp.int32),
        interpret=interpret,
    )(rows, neigh_rows, u_mh[:, None])
    return v_mh[:w]


# ---------------------------------------------------------------------------
# Bucketed-layout MH-move dispatch (per-degree-bucket sparse tiles)
# ---------------------------------------------------------------------------


def walk_transition_bucketed(
    bucket_ids: jnp.ndarray,  # (W,) int32 — degree bucket of each walk's node
    rows_by_bucket,  # tuple of (W, width_b) float32 P_IS tiles
    tiles_by_bucket,  # tuple of (W, width_b) int32 neighbor tiles
    u_mh: jnp.ndarray,  # (W,) float32 — the U_MH uniform per walk
    *,
    block_w: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """MH-IS move via one sparse tile launch per degree bucket.

    Each bucket pass runs :func:`walk_transition_sparse` at the bucket's
    own width; walk w keeps the result of the pass matching
    ``bucket_ids[w]`` (its other passes read the bucket's row 0 — a dummy
    the ``engine.combine_bucketed`` merge discards).  Because every bucket
    row is a column-truncation of the walk's full padded row and pads
    carry exactly 0, the inverted CDF index is unchanged and the result
    is bitwise-equal to the full-width layouts given the same uniforms.
    Returns ``v_mh`` (W,).
    """
    return combine_bucketed(
        bucket_ids,
        [
            walk_transition_sparse(
                rows, tiles, u_mh, block_w=block_w, interpret=interpret
            )
            for rows, tiles in zip(rows_by_bucket, tiles_by_bucket)
        ],
    )


def walk_transition_bucketed_compacted(
    rows_by_bucket,  # tuple of (cap_b, width_b) float32 compacted P_IS tiles
    tiles_by_bucket,  # tuple of (cap_b, width_b) int32 compacted neighbor tiles
    u_by_bucket,  # tuple of (cap_b,) float32 — U_MH uniform per lane
    walk_idx_by_bucket,  # tuple of (cap_b,) int32 — original walk index
    valid_by_bucket,  # tuple of (cap_b,) bool — lane holds a real walk
    num_walks: int,
    *,
    block_w: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """MH-IS move over *compacted* per-bucket tiles (the fast bucketed path).

    The engine's compaction pass (``engine.compact_plan`` +
    ``engine.bucket_capacities``) has already sorted the W walks by bucket
    id and gathered each bucket's walks into a ``[cap_b, width_b]`` tile,
    so — unlike :func:`walk_transition_bucketed` — each
    :func:`walk_transition_sparse` launch pays for the bucket's own walks
    only, not all W.  Results scatter back to original walk order through
    ``engine.scatter_compacted`` (capacity-slop lanes dropped), keeping
    the merge rule in exactly one place.  Per-lane arithmetic is the same
    CDF inversion over the same tile row and uniform, so outputs are
    bitwise-equal to the uncompacted dispatch per key.  Returns ``v_mh``
    ``(num_walks,)`` int32.
    """
    return scatter_compacted(
        num_walks,
        walk_idx_by_bucket,
        valid_by_bucket,
        [
            walk_transition_sparse(
                rows, tiles, u_b, block_w=block_w, interpret=interpret
            )
            for rows, tiles, u_b in zip(
                rows_by_bucket, tiles_by_bucket, u_by_bucket
            )
        ],
    )


# ---------------------------------------------------------------------------
# Ragged-layout fused kernel (true-degree flat-CSR reads, scalar prefetch)
# ---------------------------------------------------------------------------


def _ragged_kernel(
    indptr_ref,  # scalar prefetch (SMEM): (n+1,) int32 CSR row pointers
    nodes_ref,  # (block_w,) int32 current node per walk (SMEM)
    u_ref,  # (3 + r, block_w) f32 uniforms, walk-minor (SMEM); slot
    #   U_DIST already holds the resolved jump distance d
    cdf_ref,  # (rows, 128) f32 flat per-edge CDF, row-major (VMEM)
    idx_ref,  # (rows, 128) int32 flat CSR neighbor ids, row-major (VMEM)
    out_ref,  # (block_w,) int32 (SMEM)
    hops_ref,  # (block_w,) int32 (SMEM)
    *,
    block_w: int,
):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def load1(ref, at):
        # one element of a flat buffer: a dynamic-row vector load, then a
        # one-hot lane reduction to a scalar (x + 0 is exact, so the value
        # is bit for bit the stored one)
        row = ref[pl.ds(at // _LANES, 1), :]
        return jnp.sum(jnp.where(lane == at % _LANES, row, jnp.zeros_like(row)))

    def row_of(v):
        start = indptr_ref[v]
        return start, indptr_ref[v + 1] - start

    def one_walk(w, carry):
        def mh_move(v):
            # binary search of the row's true-degree CDF segment — the
            # arithmetic of engine.ragged_mh_invert; a probe with lo >= hi
            # changes nothing there, so stopping at lo == hi is the same
            start, deg = row_of(v)
            t = u_ref[U_MH, w] * load1(cdf_ref, start + deg - 1)

            def probe(lohi):
                lo, hi = lohi
                mid = (lo + hi) // 2  # < hi <= deg: no clamp needed
                below = load1(cdf_ref, start + mid) < t
                return jnp.where(below, mid + 1, lo), jnp.where(below, hi, mid)

            lo, _ = jax.lax.while_loop(
                lambda lohi: lohi[0] < lohi[1], probe, (jnp.int32(0), deg)
            )
            return load1(idx_ref, start + jnp.minimum(lo, deg - 1)), jnp.int32(1)

        def levy_jump(v):
            # d uniform hops from the flat CSR — the csr= arithmetic of
            # engine.levy_jump_batched
            d = u_ref[U_DIST, w].astype(jnp.int32)

            def hop(j, v_cur):
                start, deg = row_of(v_cur)
                k = jnp.minimum(
                    (u_ref[U_HOP0 + j, w] * deg.astype(jnp.float32)).astype(
                        jnp.int32
                    ),
                    deg - 1,
                )
                return load1(idx_ref, start + k)

            return jax.lax.fori_loop(0, d, hop, v), d

        nxt, hops = jax.lax.cond(
            u_ref[U_JUMP, w] > 0.5, levy_jump, mh_move, nodes_ref[w]
        )
        out_ref[w] = nxt
        hops_ref[w] = hops
        return carry

    jax.lax.fori_loop(0, block_w, one_walk, 0)


@functools.partial(
    jax.jit, static_argnames=("p_d", "r", "block_w", "interpret")
)
def walk_transition_ragged(
    nodes: jnp.ndarray,  # (W,) int32
    indptr: jnp.ndarray,  # (n+1,) int32 CSR row pointers
    indices: jnp.ndarray,  # (nnz,) int32 flat CSR neighbor ids
    edge_cdf: jnp.ndarray,  # (nnz,) float32 flat per-edge CDF
    uniforms: jnp.ndarray,  # (W, 3 + r) float32, slot 0 = jump flag
    *,
    p_d: float,
    r: int,
    block_w: int = 1024,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fused true-degree MHLJ step — one scalar-core pass per walk tile.

    ``indptr`` is scalar-prefetched into SMEM, the walk tile's nodes and
    uniforms ride in SMEM blocks, and the flat ``edge_cdf``/``indices``
    buffers sit in VMEM as ``(rows, 128)`` arrays.  Each walk takes only
    its own branch: an MH move binary-searches its CDF segment at its
    *true* degree (at most ``ceil(log2(deg + 1))`` probes), a jump runs its
    d hops from the flat CSR.  Per-walk arithmetic mirrors
    ``engine.ragged_mh_invert`` + ``engine.levy_jump_batched(csr=)`` +
    ``engine.combine_mh_jump``, so outputs are bitwise-equal to every
    other layout per key.  The jump distance d is resolved here, outside
    the kernel, by the same :func:`trunc_geom_icdf` XLA computation the
    scan backend runs, so the ``log1p`` never has to agree across
    compilers.

    Capacity on a TPU v5e: ``indptr`` must fit SMEM (1 MiB, so n up to
    about 200k), and both flat buffers must fit the scoped VMEM together
    (16 MiB by default, so nnz up to about 2M).  Larger graphs need an
    HBM + DMA variant.  Compiled walk tiles are a multiple of 1024 walks
    (the TPU tiling of a 1-D 32-bit array) unless one tile holds all W.

    Returns ``(next_nodes, hops)``, both (W,) int32.
    """
    w = nodes.shape[0]
    bw = min(block_w, w)
    if not interpret and bw < w:
        bw = min(-(-bw // _SMEM_TILE) * _SMEM_TILE, w)
    w_pad = -(-w // bw) * bw
    d = trunc_geom_icdf(uniforms[:, U_DIST], p_d, r)
    uniforms = uniforms.at[:, U_DIST].set(d.astype(jnp.float32))
    if w_pad != w:
        # padded lanes make an MH move on node 0 and are sliced off below
        nodes = jnp.pad(nodes, (0, w_pad - w))
        uniforms = jnp.pad(uniforms, ((0, w_pad - w), (0, 0)))
    nnz = edge_cdf.shape[0]
    rows = -(-nnz // _SMEM_TILE) * (_SMEM_TILE // _LANES)

    def flat_rows(x):
        return jnp.pad(x, (0, rows * _LANES - nnz)).reshape(rows, _LANES)

    tile = pl.BlockSpec((bw,), lambda i, *_: (i,), memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # indptr
        grid=(w_pad // bw,),
        in_specs=[
            tile,
            pl.BlockSpec(
                (num_uniforms(r), bw),
                lambda i, *_: (0, i),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[tile, tile],
    )
    next_nodes, hops = pl.pallas_call(
        functools.partial(_ragged_kernel, block_w=bw),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((w_pad,), jnp.int32),
            jax.ShapeDtypeStruct((w_pad,), jnp.int32),
        ],
        interpret=interpret,
        name="walk_transition_ragged",
    )(
        indptr.astype(jnp.int32),
        nodes.astype(jnp.int32),
        uniforms.T,
        flat_rows(edge_cdf),
        flat_rows(indices.astype(jnp.int32)),
    )
    return next_nodes[:w], hops[:w]
