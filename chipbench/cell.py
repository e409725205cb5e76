"""What every operation's driver shares: the inputs of a run, the
program's engine over them, the warm-up, and the walk check.

An operation (``chipbench/ops/<op>.py``) subclasses :class:`Calls` and
adds ``launch`` (one call, returning its device outputs), ``compare``,
``control``, ``failed_calls`` and ``trace_counts``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen
from chipbench.reference.walk import WalkReference, call_uniforms


class Calls:
    """A cell's inputs from its configuration and ``--seed``, the
    program's ragged engine over them, and the calls made so far."""

    def __init__(self, spec: dict, seed: int):
        from repro.core.engine import WalkEngine
        from repro.core.graphs import from_edges
        from repro.core.transition import MHLJParams

        cfg, traffic = spec["config"], spec["traffic"]
        self.chain = cfg["chain"]
        self.steps = int(traffic["steps_per_call"])
        self.warmup_calls = int(traffic["warmup_calls"])
        self.phases = {}

        t = time.perf_counter()
        self.graph = gen.make_graph(cfg)
        self.seeds = gen.split_seed(seed)
        self.data = gen.make_data(self.graph.n, cfg["data"], self.seeds.data)
        self.walkers = int(traffic["walks_per_node"]) * self.graph.n
        self.units_per_call = self.walkers * self.steps
        self.compared_calls = max(
            1, -(-int(traffic["compared_walker_steps"]) // self.units_per_call))
        self.starts = self.every_node(self.seeds.starts.permutation(self.graph.n))
        self.phases["inputs_s"] = time.perf_counter() - t

        t = time.perf_counter()
        program_graph = from_edges(self.graph.n, self.graph.src, self.graph.dst,
                                   layout="ragged")
        self.phases["program_graph_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = jax.block_until_ready(WalkEngine.from_graph(
            program_graph, MHLJParams(**self.chain),
            lipschitz=jnp.asarray(self.data.lipschitz, jnp.float32),
            layout=cfg["engine"]["layout"], backend=cfg["engine"]["backend"],
        ))
        self.phases["engine_s"] = time.perf_counter() - t
        self.calls_made = 0
        self.keys, self.outputs = {}, {}

    def every_node(self, perm):
        """Start nodes of one walk per node per ``walks_per_node``, in the
        order of the permutation ``perm`` of the nodes."""
        return np.tile(perm, self.walkers // self.graph.n).astype(np.int32)

    def next_input(self, i):
        return self.seeds.call_key(i)

    def warmup(self):
        """The first calls, the first of which compiles (or loads from the
        persistent cache)."""
        t = time.perf_counter()
        for _ in range(self.warmup_calls):
            jax.block_until_ready(self.launch(self.next_input(self.calls_made)))
            self.phases.setdefault("first_call_s", time.perf_counter() - t)
        self.phases["warmup_s"] = time.perf_counter() - t

    def sample(self, calls, rng):
        k = min(self.compared_calls, len(calls))
        return sorted(int(i) for i in rng.choice(calls, size=k, replace=False))

    def reference(self, dtype=np.float64) -> WalkReference:
        c = self.chain
        return WalkReference(self.graph.indptr, self.graph.indices,
                             self.data.lipschitz, c["p_j"], c["p_d"], c["r"], dtype=dtype)

    def uniforms(self, i):
        return call_uniforms(self.keys[i], self.walkers, self.steps, self.chain["r"])

    def walk_stream(self, i):
        """(W, T) update nodes and hop counts of call ``i`` on the host."""
        raise NotImplementedError

    def judge_walk(self, chosen) -> float:
        """Share of the chosen calls' transitions that disagree with the
        law.  A call's last step is judged against the next call's first
        node where the next call starts from it and was kept; otherwise
        by its hop count alone."""
        ref = self.reference()
        bad = judged = 0
        for i in chosen:
            nodes, hops = self.walk_stream(i)
            first = self.next_first(i)
            b, j = ref.mismatches(nodes, hops, self.uniforms(i), next_first=first)
            bad, judged = bad + b, judged + j
        return bad / judged

    def next_first(self, i):
        return None

    def walk_control(self, chosen) -> float:
        """The same share for the bfloat16 reference put in the program's
        place, stepping from the chosen calls' nodes."""
        import ml_dtypes

        ref, ctl = self.reference(), self.reference(dtype=ml_dtypes.bfloat16)
        bad = judged = 0
        for i in chosen:
            b, j = ref.disagreements(ctl, self.walk_stream(i)[0], self.uniforms(i))
            bad, judged = bad + b, judged + j
        return bad / judged

    def bad_walk(self, nodes, hops) -> bool:
        return bool(nodes.min() < 0 or nodes.max() >= self.graph.n
                    or hops.min() < 1 or hops.max() > self.chain["r"])
