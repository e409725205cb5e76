"""The work a cell's calls require, counted from shapes and from what the
calls returned.  These are the numerators of the roofline and MFU
metrics; they count what the algorithm must do, not what an
implementation happens to do, so every backend is judged on one count.
"""
from __future__ import annotations

import numpy as np

WORD = 4  # bytes of an int32 node id, row pointer, or float32 CDF entry


def walk_bytes(deg, jump, dist) -> int:
    """Least bytes that MHLJ transitions must move, summed over walker-steps.

    ``deg`` is the degree (self-loop included) of each walker-step's
    current node, ``jump`` whether that step jumped and ``dist`` its jump
    distance (any value where it did not jump).  Every walker-step reads
    its node and writes its next node and hop count (3 words).  An MH
    move reads the row's two pointers and its CDF total, binary-searches
    the other deg - 1 CDF entries (ceil(log2(deg)) probes) and reads the
    chosen neighbour id.  A jump reads two row pointers and one neighbour
    id per hop.  Uniforms are generated, not moved, and count nothing.
    """
    deg = np.asarray(deg, np.int64)
    jump = np.asarray(jump, bool)
    dist = np.asarray(dist, np.int64)
    probes = np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
    mh = 2 + 1 + probes + 1
    words = 3 + np.where(jump, 3 * dist, mh)
    return int(words.sum()) * WORD


def walk_flops(jump, dist) -> int:
    """Floating-point operations of the same transitions: one product
    u * total for an MH move; log1p, a product, a quotient and a ceil for
    the jump distance and one product per hop for a jump."""
    jump = np.asarray(jump, bool)
    dist = np.asarray(dist, np.int64)
    return int(np.where(jump, 4 + dist, 1).sum())


def fleet_step_flops(n: int, dim: int, walkers: int, avg_every: int) -> float:
    """Operations one fleet step requires.

    Per walker, the SGD step on its node's row: the dot A_v . x (2 dim),
    the residual (1), the coefficient -2 gamma w_v r (3) and the update
    x - c A_v (2 dim).  The per-walker loss over all n rows: A x_w
    (2 n dim), the residual, its square and the sum (3 n), and the mean
    (1).  The averaged model: its mean over walkers (dim W + dim) and its
    loss (2 n dim + 3 n + 1).  The average that replaces every model,
    once every ``avg_every`` steps (dim W + dim).
    """
    per_walker = (4 * dim + 4) + (2 * n * dim + 3 * n + 1)
    averaged = (dim * walkers + dim) + (2 * n * dim + 3 * n + 1)
    averaging = (dim * walkers + dim) / avg_every if avg_every else 0.0
    return float(walkers * per_walker + averaged + averaging)
