"""JAX random-walk simulators (paper §II.C + Algorithm 1).

The MHLJ transition itself lives in :mod:`repro.core.engine` — the single
source of truth for Algorithm 1 — and the simulators here are thin
trajectory-shaped consumers of :class:`~repro.core.engine.WalkEngine`:

* :func:`walk_markov` — a generic 1-hop time-homogeneous chain given padded
  per-row probabilities (covers simple RW, MH-uniform, MH-IS).  Not an MHLJ
  variant, so it does not route through the engine.
* :func:`walk_mhlj` — Algorithm 1 exactly, via ``WalkEngine.run``: per
  iteration flip J~Ber(p_J); J=0 -> one MH-IS hop; J=1 -> d~TruncGeom(p_d, r)
  uniform hops without updates.  Returns the sequence of *update* nodes v_t
  plus the number of physical transitions per iteration (Remark-1
  accounting).
* :func:`walk_mhlj_batched` — W parallel walks in one batched engine run
  (a single vectorized transition per step, not W scans).

``p_j`` may be a scalar or a (T,) schedule array (Fig 6 annealing).

Representation: graphs enter as padded neighbor tensors ``neighbors`` of shape
(n, max_deg) with degree vector ``degrees`` (see ``core.graphs``); 1-hop
transition rows enter as (n, max_deg) probabilities aligned with neighbors.
"""
from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import WalkEngine

__all__ = [
    "graph_tensors",
    "walk_markov",
    "walk_mhlj",
    "walk_markov_batched",
    "walk_mhlj_batched",
]


def graph_tensors(graph) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device tensors (neighbors int32 (n,max_deg), degrees int32 (n,)).

    Accepts a dense :class:`~repro.core.graphs.Graph` or an O(E)
    :class:`~repro.core.graphs.CSRGraph` — both carry the same padded
    neighbor tensors, so every simulator here runs on either.  A
    :class:`~repro.core.graphs.BucketedCSRGraph` deliberately has no full
    padded tensor; build the engine from it directly
    (``WalkEngine.from_graph``) instead of materializing one here.
    """
    if not hasattr(graph, "neighbors"):
        raise TypeError(
            "graph has no padded neighbor tensor (bucketed layout?); use "
            "WalkEngine.from_graph(graph, ...) or graph.to_csr() instead"
        )
    return jnp.asarray(graph.neighbors), jnp.asarray(graph.degrees)


def _categorical_padded(key, probs_row: jnp.ndarray) -> jnp.ndarray:
    """Sample an index from a padded probability row (pads have prob 0)."""
    logits = jnp.log(jnp.maximum(probs_row, 1e-38))
    logits = jnp.where(probs_row > 0, logits, -jnp.inf)
    return jax.random.categorical(key, logits)


@functools.partial(jax.jit, static_argnames=("num_steps",))
def walk_markov(
    key: jax.Array,
    row_probs: jnp.ndarray,  # (n, max_deg) float, aligned with neighbors
    neighbors: jnp.ndarray,  # (n, max_deg) int32
    v0: Union[int, jnp.ndarray],
    num_steps: int,
) -> jnp.ndarray:
    """Simulate a 1-hop chain; returns trajectory (num_steps+1,) of node ids."""

    def step(carry, key_t):
        v = carry
        idx = _categorical_padded(key_t, row_probs[v])
        v_next = neighbors[v, idx]
        return v_next, v_next

    keys = jax.random.split(key, num_steps)
    v0 = jnp.asarray(v0, dtype=jnp.int32)
    _, traj = jax.lax.scan(step, v0, keys)
    return jnp.concatenate([v0[None], traj])


@functools.partial(jax.jit, static_argnames=("num_steps", "r", "p_d"))
def walk_mhlj(
    key: jax.Array,
    is_row_probs: jnp.ndarray,  # (n, max_deg) P_IS rows
    neighbors: jnp.ndarray,  # (n, max_deg)
    degrees: jnp.ndarray,  # (n,)
    v0: Union[int, jnp.ndarray],
    num_steps: int,
    p_j: Union[float, jnp.ndarray],
    p_d: float,
    r: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 1's node sequence (single walk), via the unified engine.

    Returns:
      update_nodes: (num_steps,) int32 — v_t at which update t is applied
        (element t is the node holding the model when update t runs; the
        first update runs at v0).
      transitions: (num_steps,) int32 — physical hops taken after update t
        (1 for an MH move, d for a jump) — Remark-1 accounting.
    """
    engine = WalkEngine(
        neighbors=neighbors,
        degrees=degrees,
        p_d=p_d,
        r=r,
        row_probs=is_row_probs,
        backend="scan",
    )
    return engine.run(key, jnp.asarray(v0, jnp.int32), num_steps, p_j=p_j)


def walk_markov_batched(key, row_probs, neighbors, v0s, num_steps):
    """vmap over independent walks; v0s: (w,) -> trajectories (w, num_steps+1)."""
    keys = jax.random.split(key, v0s.shape[0])
    return jax.vmap(walk_markov, in_axes=(0, None, None, 0, None))(
        keys, row_probs, neighbors, v0s, num_steps
    )


@functools.partial(
    jax.jit, static_argnames=("num_steps", "r", "p_d", "backend")
)
def walk_mhlj_batched(
    key,
    is_row_probs,
    neighbors,
    degrees,
    v0s,
    num_steps,
    p_j,
    p_d,
    r,
    backend: str = "auto",
):
    """W Algorithm-1 walks in one batched engine run.

    One vectorized transition services all W walks per step (the
    engine's resolved backend: scan under ``"auto"``); returns (w, num_steps)
    update nodes + hops.
    """
    engine = WalkEngine(
        neighbors=neighbors,
        degrees=degrees,
        p_d=p_d,
        r=r,
        row_probs=is_row_probs,
        backend=backend,
    )
    return engine.run(key, v0s, num_steps, p_j=p_j)


def empirical_distribution(update_nodes: np.ndarray, n: int, burn_in: int = 0) -> np.ndarray:
    """Empirical visit distribution of the update sequence after burn-in."""
    seq = np.asarray(update_nodes)[..., burn_in:].ravel()
    counts = np.bincount(seq, minlength=n).astype(np.float64)
    return counts / counts.sum()
