"""Plain reference of one MHLJ transition (Algorithm 1 of arXiv:2407.20611).

Given a walker's node v and its uniforms, the chain jumps with
probability p_J; a jump draws d ~ TruncGeom(p_d, r) and takes d uniform
neighbour hops, and otherwise the walker takes one Metropolis-Hastings
move towards the importance target pi(v) ~ L_v (Eq. 7):

    P(v, u) = min(1 / deg(v), L_u / (deg(u) L_v))   for u != v on an edge,
    P(v, v) = 1 - sum of the above.

The move is the first neighbour whose row CDF reaches u_mh times the
row's total.  Rows are the graph's CSR rows (neighbours ascending, the
self-loop included), so the same uniforms pick the same neighbour as any
implementation of the law over that order.

Uniforms follow the walk engine's documented stream: a call's key is
split into one key per step, and step t draws ``uniform(key_t, (W, 3 +
r))`` with columns ``[jump, mh, distance, hop_1 .. hop_r]``.  The
arithmetic here is numpy in the precision asked for (float64 by default);
``dtype=ml_dtypes.bfloat16`` is the lower-precision control.  Nothing of
the program is imported.
"""
from __future__ import annotations

import math

import jax
import numpy as np

U_JUMP, U_MH, U_DIST, U_HOP0 = 0, 1, 2, 3


def call_uniforms(key, walkers: int, steps: int, r: int) -> np.ndarray:
    """(steps, W, 3 + r) float32 uniforms of one call of ``steps`` steps."""
    keys = jax.random.split(np.asarray(key, np.uint32), steps)
    draw = jax.vmap(lambda k: jax.random.uniform(k, (walkers, 3 + r), np.float32))
    return np.asarray(draw(keys))


class WalkReference:
    """The transition law of one graph and Lipschitz vector."""

    def __init__(self, indptr, indices, lipschitz, p_j, p_d, r, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices, np.int64)
        self.deg = np.diff(self.indptr)
        self.n = self.deg.size
        self.p_j, self.p_d, self.r = np.float32(p_j), float(p_d), int(r)
        self.cdf = self._row_cdf(np.asarray(lipschitz, np.float64))
        self.total = self.cdf[self.indptr[1:] - 1]
        self.rounds = max(1, math.ceil(math.log2(int(self.deg.max()) + 1)))

    def _row_cdf(self, lips) -> np.ndarray:
        dt = self.dtype
        src = np.repeat(np.arange(self.n), self.deg)
        dst = self.indices
        deg = self.deg.astype(dt)
        lv = lips.astype(dt)
        one = dt.type(1)
        move = np.minimum(one / deg[src], lv[dst] / (deg[dst] * lv[src])).astype(dt)
        move[dst == src] = 0
        # rows grouped by degree, so each group is one (rows, k) cumsum
        cdf = np.empty(self.indices.size, dt)
        for k in np.unique(self.deg):
            rows = np.nonzero(self.deg == k)[0]
            pos = self.indptr[rows][:, None] + np.arange(k)[None, :]
            p = move[pos]
            is_self = self.indices[pos] == rows[:, None]
            stay = (one - p.sum(axis=1, dtype=dt)).astype(dt)
            p = np.where(is_self, stay[:, None], p).astype(dt)
            p = np.maximum(p, dt.type(0))
            cdf[pos] = np.cumsum(p, axis=1, dtype=dt)
        return cdf

    def _distance(self, u):
        dt = self.dtype
        z = dt.type(1.0 - (1.0 - self.p_d) ** self.r)
        log_q = dt.type(math.log(1.0 - self.p_d))
        x = np.log1p((-u.astype(dt) * z).astype(dt)).astype(dt) / log_q
        return np.clip(np.ceil(x.astype(np.float64)), 1, self.r).astype(np.int64)

    def step(self, nodes, u):
        """Next nodes and hop counts of W walkers; ``u`` is (W, 3 + r)."""
        dt = self.dtype
        v = np.asarray(nodes, np.int64)
        start, deg = self.indptr[v], self.deg[v]
        target = (u[:, U_MH].astype(dt) * self.total[v]).astype(dt)
        lo, hi = np.zeros_like(deg), deg.copy()
        for _ in range(self.rounds):
            active = lo < hi
            mid = (lo + hi) // 2
            below = active & (self.cdf[start + np.minimum(mid, deg - 1)] < target)
            lo = np.where(below, mid + 1, lo)
            hi = np.where(active & ~below, mid, hi)
        v_mh = self.indices[start + np.minimum(lo, deg - 1)]

        d = self._distance(u[:, U_DIST])
        cur = v
        for i in range(self.r):
            dc = self.deg[cur]
            k = np.floor((u[:, U_HOP0 + i].astype(dt) * dc.astype(dt)).astype(np.float64))
            k = np.minimum(k.astype(np.int64), dc - 1)
            cur = np.where(i < d, self.indices[self.indptr[cur] + k], cur)
        jump = u[:, U_JUMP] < self.p_j
        return np.where(jump, cur, v_mh), np.where(jump, d, 1)

    def mismatches(self, nodes, hops, uniforms, next_first=None):
        """Transitions of one call that disagree with the law.

        ``nodes``/``hops`` are the call's (W, T) update nodes and hop
        counts.  Each step is judged from the program's own node at that
        step, so one disagreement does not carry into the steps after it.
        Step t's next node is ``nodes[:, t + 1]``, and the last step's is
        ``next_first`` where the caller knows it (the first node of the
        next call); otherwise only its hop count is judged.  Returns
        (mismatched transitions, transitions judged).
        """
        steps = nodes.shape[1]
        bad = judged = 0
        for t in range(steps):
            nxt, h = self.step(nodes[:, t], uniforms[t])
            wrong = h != hops[:, t]
            if t + 1 < steps:
                wrong |= nxt != nodes[:, t + 1]
            elif next_first is not None:
                wrong |= nxt != next_first
            bad += int(wrong.sum())
            judged += wrong.size
        return bad, judged

    def disagreements(self, other, nodes, uniforms):
        """Transitions in which ``other`` (the control) steps differently
        from this reference, both stepping from the program's nodes of one
        call.  Returns (disagreeing transitions, transitions judged)."""
        bad = judged = 0
        for t in range(nodes.shape[1]):
            a = self.step(nodes[:, t], uniforms[t])
            b = other.step(nodes[:, t], uniforms[t])
            wrong = (a[0] != b[0]) | (a[1] != b[1])
            bad += int(wrong.sum())
            judged += wrong.size
        return bad, judged
