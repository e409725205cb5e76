"""Pure-jnp oracle for the walk-transition kernels (same pre-drawn uniforms).

The oracles *are* the engine's scan-backend math — re-exported here so the
kernel directory keeps the kernel/ops/ref layout of its siblings while
Algorithm 1 stays implemented exactly once (repro.core.engine).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine import (
    U_MH,
    combine_bucketed,
    combine_mh_jump,
    edge_cdf_tiles,
    levy_jump_batched,
    mh_cdf_invert,
    mhlj_transition_math,
    ragged_mh_invert,
    scatter_compacted,
)


def walk_transition_ref(
    nodes: jnp.ndarray,
    row_probs: jnp.ndarray,
    neighbors: jnp.ndarray,
    degrees: jnp.ndarray,
    uniforms: jnp.ndarray,
    *,
    p_d: float,
    r: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Same contract as ``kernel.walk_transition`` (slot 0 = jump flag)."""
    return mhlj_transition_math(
        nodes, row_probs[nodes], neighbors, degrees, uniforms, p_d, r
    )


def walk_transition_sparse_ref(
    rows: jnp.ndarray, neigh_rows: jnp.ndarray, u_mh: jnp.ndarray
) -> jnp.ndarray:
    """Same contract as ``kernel.walk_transition_sparse`` — the engine's
    vectorized CDF inversion over gathered tiles."""
    return mh_cdf_invert(rows, neigh_rows, u_mh)


def walk_transition_bucketed_ref(
    bucket_ids: jnp.ndarray,
    rows_by_bucket,
    tiles_by_bucket,
    u_mh: jnp.ndarray,
) -> jnp.ndarray:
    """Same contract as ``kernel.walk_transition_bucketed``: per-bucket CDF
    inversion with each walk keeping its own bucket's result (merge rule:
    ``engine.combine_bucketed``)."""
    return combine_bucketed(
        bucket_ids,
        [
            mh_cdf_invert(rows, tiles, u_mh)
            for rows, tiles in zip(rows_by_bucket, tiles_by_bucket)
        ],
    )


def walk_transition_ragged_ref(
    nodes: jnp.ndarray,
    indptr: jnp.ndarray,
    degrees: jnp.ndarray,
    indices: jnp.ndarray,
    edge_cdf: jnp.ndarray,
    uniforms: jnp.ndarray,
    *,
    p_d: float,
    r: int,
    max_degree: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Same contract as ``kernel.walk_transition_ragged``: the engine's
    tiled lower-bound search of the flat CDF (``engine.ragged_mh_invert``
    over ``engine.edge_cdf_tiles``), the CSR-gathered Lévy branch and the
    jump/MH combine — the fused kernel mirrors this composition per walk,
    finding the same lower bound by binary search."""
    v_mh = ragged_mh_invert(
        indptr, degrees, indices, edge_cdf,
        edge_cdf_tiles(edge_cdf, max_degree=max_degree),
        nodes, uniforms[:, U_MH],
    )
    v_jump, d = levy_jump_batched(
        nodes, uniforms, None, degrees, p_d, r, csr=(indptr, indices)
    )
    return combine_mh_jump(v_mh, v_jump, d, uniforms)


def walk_transition_bucketed_compacted_ref(
    rows_by_bucket,
    tiles_by_bucket,
    u_by_bucket,
    walk_idx_by_bucket,
    valid_by_bucket,
    num_walks: int,
) -> jnp.ndarray:
    """Same contract as ``kernel.walk_transition_bucketed_compacted``: the
    engine's CDF inversion over each compacted ``[cap_b, width_b]`` tile,
    merged back to walk order by ``engine.scatter_compacted``."""
    return scatter_compacted(
        num_walks,
        walk_idx_by_bucket,
        valid_by_bucket,
        [
            mh_cdf_invert(rows, tiles, u_b)
            for rows, tiles, u_b in zip(
                rows_by_bucket, tiles_by_bucket, u_by_bucket
            )
        ],
    )
