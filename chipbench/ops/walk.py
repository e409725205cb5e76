"""Walk traffic: ``WalkEngine.run`` calls back to back, one pass a call.

A pass is node2vec's: one walk started at every node (``walks_per_node``
of them), ``steps_per_call`` MHLJ transitions long.  Each call draws a
new order of the start nodes from the seed and the call's index, so
every seed offers the same work in another order.  One call is W x steps
transitions (``walk_steps_per_s``); ``WalkEngine.run`` returns each
walk's update nodes, so the last transition's destination is computed
but not returned, and that step is judged by its hop count alone.

Set-up: the graph and data from the configuration and ``--seed``, the
program's ragged graph and engine (its flat CDF), and ``warmup_calls``
calls, the first of which compiles.  The checks judge a sample of the
window's calls, drawn from the seed, transition by transition against
``chipbench.reference.walk``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts
from chipbench.cell import Calls


class WalkOp(Calls):
    rate_metric = "walk_steps_per_s"

    def __init__(self, spec: dict, seed: int):
        super().__init__(spec, seed)
        steps = self.steps
        self._call = jax.jit(lambda e, key, v0: e.run(key, v0, steps))

    def next_input(self, i):
        starts = self.every_node(self.seeds.call_permutation(i, self.graph.n))
        return self.seeds.call_key(i), jnp.asarray(starts)

    def launch(self, inp):
        key, starts = inp
        out = self._call(self.engine, key, starts)
        self.keys[self.calls_made], self.outputs[self.calls_made] = key, out
        self.calls_made += 1
        return out

    def walk_stream(self, i):
        nodes, hops = self.outputs[i]
        return np.asarray(nodes), np.asarray(hops)

    def failed_calls(self, calls) -> int:
        """Calls with a node outside [0, n) or a hop count outside [1, r]."""
        return sum(self.bad_walk(*self.walk_stream(i)) for i in calls)

    def trace_counts(self, calls) -> dict:
        walk_bytes = walk_flops = 0
        for i in calls:
            nodes, hops = self.walk_stream(i)
            jump = (self.uniforms(i)[:, :, 0] < np.float32(self.chain["p_j"])).T
            walk_bytes += counts.walk_bytes(self.graph.degrees[nodes], jump, hops)
            walk_flops += counts.walk_flops(jump, hops)
        return {"walk_bytes": walk_bytes, "walk_flops": walk_flops,
                "walker_steps": self.units_per_call * len(calls)}

    def compare(self, calls, rng) -> dict:
        self.chosen = self.sample(calls, rng)
        self.outputs = {i: self.walk_stream(i) for i in self.chosen}
        self.engine = self._call = None
        return {"walk_mismatch_share": self.judge_walk(self.chosen)}

    def control(self) -> dict:
        return {"walk_mismatch_share": self.walk_control(self.chosen)}


def build(spec: dict, seed: int) -> WalkOp:
    return WalkOp(spec, seed)
