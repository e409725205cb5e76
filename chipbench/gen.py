"""Inputs of a benchmark run, made from a configuration and ``--seed``.

The graph is fixed by the configuration's own ``graph.seed``; the
regression data, the walkers' start nodes and the walk keys come from
``--seed``.  Everything here is numpy on the host and imports nothing of
the program, so the references in ``chipbench/reference`` can read the
same inputs without taking anything the program made.

The generators follow published laws: the Graph500 Kronecker generator
(the specification's own reference code: initiator A, B, C = 0.57, 0.19,
0.19, ``edgefactor`` x 2^scale edges, vertex and edge permutations), and
the paper's 2-D grid and Appendix-D heterogeneous regression, which the
program's ``core.graphs`` and ``data.synthetic`` also make;
``tests/chipbench`` checks that those agree.  A walk is defined on a
connected graph, so a generator whose graph is not connected (Kronecker
leaves isolated vertices and small components) is cut to its largest
connected component, relabelled in ascending order of the generated ids.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected edge list and the CSR the walk law is defined on.

    ``indices`` holds each row's neighbours in ascending order, the node's
    own self-loop included, with duplicates removed: the order in which
    the chain's uniforms pick a neighbour.
    """

    n: int
    src: np.ndarray  # (E,) int64 undirected edges: unique pairs, src < dst
    dst: np.ndarray
    indptr: np.ndarray  # (n+1,) int64
    indices: np.ndarray  # (nnz,) int32
    degrees: np.ndarray  # (n,) int32, self-loop included

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


@dataclasses.dataclass(frozen=True)
class Data:
    """Appendix-D regression data: one row per node."""

    features: np.ndarray  # (n, dim) float64
    targets: np.ndarray  # (n,) float64
    lipschitz: np.ndarray  # (n,) float64, L_v = 2 ||A_v||^2


def _kronecker_bits(scale: int, m: int, initiator, rng: np.random.Generator):
    """m edges' endpoints before relabelling: for each bit, the quadrant
    (src bit, dst bit) is (0, 0), (0, 1), (1, 0), (1, 1) with probability
    A, B, C, D of the 2 x 2 initiator."""
    a, b, c = (float(x) for x in initiator[:3])
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        src_bit = rng.random(m) > ab
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    return src, dst


def _kronecker_edges(scale: int, edgefactor: int, initiator, seed: int):
    """Graph500 Kronecker edge list of edgefactor x 2^scale edges, with the
    vertex labels and the edge order permuted, as the specification's
    generator does."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    src, dst = _kronecker_bits(scale, m, initiator, rng)
    label = rng.permutation(n)
    order = rng.permutation(m)
    return n, label[src][order], label[dst][order]


def _largest_component(n: int, src, dst):
    """The edges of the largest connected component, relabelled 0..k-1 in
    ascending order of the old ids; returns (k, src, dst)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    adj = coo_matrix((np.ones(src.size, np.int8), (src, dst)), shape=(n, n))
    _, label = connected_components(adj, directed=False)
    keep = label == np.bincount(label).argmax()
    new_id = np.cumsum(keep) - 1
    on = keep[src] & keep[dst]
    return int(keep.sum()), new_id[src[on]], new_id[dst[on]]


def _grid2d_edges(rows: int, cols: int):
    ids = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    src = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    dst = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    return src, dst


def make_graph(cfg: dict) -> Graph:
    """The configuration's graph as an undirected edge list (unique pairs,
    no self-loops) and its CSR."""
    family = cfg["family"]
    if family == "kronecker":
        n, src, dst = _kronecker_edges(int(cfg["scale"]), int(cfg["edgefactor"]),
                                       cfg["initiator"], int(cfg["graph_seed"]))
        n, src, dst = _largest_component(n, src, dst)
    elif family == "grid2d":
        n = int(cfg["rows"]) * int(cfg["cols"])
        src, dst = _grid2d_edges(int(cfg["rows"]), int(cfg["cols"]))
    else:
        raise ValueError(f"unknown graph family {family!r}")
    off = src != dst
    pairs = np.unique(np.minimum(src, dst)[off] * n + np.maximum(src, dst)[off])
    src, dst = pairs // n, pairs % n
    loops = np.arange(n, dtype=np.int64)
    a = np.concatenate([src, dst, loops])
    b = np.concatenate([dst, src, loops])
    codes = np.unique(a * n + b)
    degrees = np.bincount(codes // n, minlength=n).astype(np.int32)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(degrees, out=indptr[1:])
    return Graph(n, src, dst, indptr, (codes % n).astype(np.int32), degrees)


def make_data(n: int, spec: dict, rng: np.random.Generator) -> Data:
    """Appendix D: A_v ~ N(0, s_v^2 I), s_v^2 = sigma_high_sq with
    probability p_high (at least one such node), else sigma_low_sq;
    y_v = A_v . x* + N(0, 1) with x* ~ N(0, x_star_scale^2 I)."""
    dim = int(spec["dim"])
    x_star = float(spec["x_star_scale"]) * rng.normal(size=dim)
    mask = rng.random(n) < float(spec["p_high"])
    if not mask.any():
        mask[rng.choice(n, size=1, replace=False)] = True
    scale = np.where(
        mask, np.sqrt(float(spec["sigma_high_sq"])), np.sqrt(float(spec["sigma_low_sq"]))
    )
    features = rng.normal(size=(n, dim)) * scale[:, None]
    targets = features @ x_star + rng.normal(size=n)
    return Data(features, targets, 2.0 * (features**2).sum(axis=-1))


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Independent streams of one ``--seed``: data and start nodes, and
    per call a raw threefry key and a permutation, each derived from the
    seed and the call's index alone."""

    seed: int
    data: np.random.Generator
    starts: np.random.Generator

    def call_key(self, i: int) -> np.ndarray:
        """(2,) uint32 raw key of call ``i``."""
        return np.random.SeedSequence(self.seed, spawn_key=(2, i)).generate_state(
            2, np.uint32)

    def call_permutation(self, i: int, n: int) -> np.ndarray:
        """A permutation of 0..n-1 for call ``i``."""
        rng = np.random.default_rng(np.random.SeedSequence(self.seed, spawn_key=(3, i)))
        return rng.permutation(n)


def split_seed(seed: int) -> Seeds:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    data, starts = np.random.SeedSequence(seed).spawn(2)
    return Seeds(seed, np.random.default_rng(data), np.random.default_rng(starts))
