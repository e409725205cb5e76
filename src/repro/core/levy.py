"""Lévy jump machinery (paper §V).

The jump distance is drawn from a truncated geometric distribution

    P(D = d) = p_d (1 - p_d)^{d-1} / (1 - (1 - p_d)^r),   1 <= d <= r,

and the jump itself performs ``d`` consecutive *uniform* simple-random-walk
hops with no model updates.  The induced one-shot transition matrix has the
closed form (paper Eq. in §V / Appendix A):

    P_Lévy = sum_{i=1..r} w_i * diag(A^i 1)^{-1} A^i,
    w_i = p_d (1 - p_d)^{i-1} / (1 - (1 - p_d)^r).

NOTE on the closed form: the paper composes *adjacency powers* (A^i row-
normalized), which counts i-hop *paths*; the simulated jump chains i uniform
single hops, i.e. D^i where D = diag(A 1)^{-1} A.  On regular graphs the two
coincide; on irregular graphs they differ slightly.  We implement BOTH
(``levy_matrix`` = paper closed form, ``levy_matrix_chained`` = exact law of
Algorithm 1's jump loop) and use the chained form for simulation-faithful
analysis, the paper form for reproducing Theorem-1 constants.  The discrepancy
is surfaced in tests and EXPERIMENTS.md.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.graphs import Graph

__all__ = [
    "trunc_geom_pmf",
    "trunc_geom_mean",
    "trunc_geom_icdf",
    "levy_weights",
    "levy_matrix",
    "levy_matrix_chained",
    "expected_transitions_per_update",
]


def trunc_geom_pmf(p_d: float, r: int) -> np.ndarray:
    """PMF of TruncGeom(p_d, r) over support {1, ..., r}."""
    if not (0.0 < p_d < 1.0):
        raise ValueError(f"p_d must be in (0,1), got {p_d}")
    if r < 1:
        raise ValueError(f"r must be >= 1, got {r}")
    d = np.arange(1, r + 1, dtype=np.float64)
    pmf = p_d * (1.0 - p_d) ** (d - 1.0)
    pmf /= 1.0 - (1.0 - p_d) ** r
    return pmf


def trunc_geom_icdf(u, p_d: float, r: int):
    """Inverse CDF of TruncGeom(p_d, r): maps U(0,1) draws to d in {1..r}.

    F(d) = (1 - (1-p_d)^d) / (1 - (1-p_d)^r), so
    d = ceil(log1p(-u * Z) / log(1 - p_d)) with Z = 1 - (1-p_d)^r.

    Pure ``jnp`` on scalars or arrays — this is the single distance-sampling
    formula shared by every backend of :mod:`repro.core.engine` (including
    the Pallas walk-transition kernel, where it traces into kernel code).
    """
    z = 1.0 - (1.0 - p_d) ** r
    d = jnp.ceil(jnp.log1p(-u * z) / jnp.log(1.0 - p_d)).astype(jnp.int32)
    return jnp.clip(d, 1, r)


def trunc_geom_mean(p_d: float, r: int) -> float:
    """E[D] for D ~ TruncGeom(p_d, r), as 1 + E[D - 1] so that rounding
    never takes it below 1."""
    pmf = trunc_geom_pmf(p_d, r)
    return 1.0 + float(np.dot(np.arange(r), pmf))


def levy_weights(p_d: float, r: int) -> np.ndarray:
    """Alias for the mixture weights w_i (identical to the pmf)."""
    return trunc_geom_pmf(p_d, r)


def levy_matrix(graph: Graph, p_d: float, r: int) -> np.ndarray:
    """Paper closed form: sum_i w_i diag(A^i 1)^{-1} A^i."""
    a = graph.adj
    w = levy_weights(p_d, r)
    out = np.zeros_like(a)
    a_pow = np.eye(graph.n)
    for i in range(1, r + 1):
        a_pow = a_pow @ a
        row_sums = a_pow.sum(axis=1, keepdims=True)
        out += w[i - 1] * (a_pow / row_sums)
    return out


def levy_matrix_chained(graph: Graph, p_d: float, r: int) -> np.ndarray:
    """Exact law of Algorithm 1's jump loop: sum_i w_i D^i, D = deg^{-1} A."""
    a = graph.adj
    d_mat = a / a.sum(axis=1, keepdims=True)
    w = levy_weights(p_d, r)
    out = np.zeros_like(a)
    d_pow = np.eye(graph.n)
    for i in range(1, r + 1):
        d_pow = d_pow @ d_mat
        out += w[i - 1] * d_pow
    return out


def expected_transitions_per_update(p_j: float, p_d: float, r: int) -> float:
    """Remark 1: exact expected node visits per SGD update, and its bound.

    Returns the exact value (1-p_J)*1 + p_J*E[D]; the paper's bound is
    1 + p_J(1/p_d - 1) and is asserted >= exact in tests.
    """
    # the same value as 1 + p_J*(E[D] - 1): never below 1 after rounding
    return 1.0 + p_j * (trunc_geom_mean(p_d, r) - 1.0)


def remark1_bound(p_j: float, p_d: float, r: int) -> float:
    """Paper Remark 1 upper bound: 1 + p_J (1/p_d - 1)."""
    del r
    return 1.0 + p_j * (1.0 / p_d - 1.0)
