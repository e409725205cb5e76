"""Fleet traffic: the W-walker RW-SGD training scan, calls back to back.

Each call is ``repro.walk_sgd.fleet.run_fleet`` over ``steps_per_call``
steps from the previous call's models and walker positions, with a fresh
key and ``start_step=0``, so no call compiles anew.  One call is W x steps
per-walker updates (``updates_per_s``), each with the per-step loss
evaluation the fleet computes.

Set-up: the graph and data from the configuration and ``--seed``, the
program's engine and fleet, and ``warmup_calls`` calls from x = 0: the
first compiles, and these calls are the ones the checks follow with the
reference, step by step.  The window continues from their state.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts
from chipbench.cell import Calls
from chipbench.reference.fleet import reference_call

LOSS_BLOCK = 1024  # walkers per block of the reference's loss evaluation


class FleetOp(Calls):
    rate_metric = "updates_per_s"

    def __init__(self, spec: dict, seed: int):
        from repro.models import regression
        from repro.walk_sgd import fleet as fleet_mod

        super().__init__(spec, seed)
        traffic = spec["traffic"]
        self.avg_every = int(traffic["avg_every"])
        self._fleet_mod = fleet_mod
        self._grad = regression.linear_grad
        lips = self.data.lipschitz
        self.gamma = float(traffic["gamma_times_mean_lipschitz"]) / float(lips.mean())
        self.weights = (lips.mean() / lips).astype(np.float32)
        self.fleet = fleet_mod.WalkFleet.create(
            self.engine, self.walkers, v0s=self.starts, avg_every=self.avg_every)
        self.args = jax.block_until_ready((
            jnp.asarray(self.data.features, jnp.float32),
            jnp.asarray(self.data.targets, jnp.float32),
            jnp.asarray(self.weights),
        ))
        self.p_j = jnp.full((self.steps,), self.chain["p_j"], jnp.float32)
        self.x = jnp.zeros((self.walkers, self.data.features.shape[1]), jnp.float32)
        stated = spec["config"]["precision"]["loss_product_inputs"]
        self.loss_inputs = jnp.dtype(stated[jax.default_backend()])

    def launch(self, key):
        x, mse, avg, nodes, hops, final = self._fleet_mod.run_fleet(
            key, self.x, *self.args, self.fleet, self.steps, self.gamma, self.p_j,
            True, self._grad,
        )
        self.x = x
        self.fleet = dataclasses.replace(self.fleet, nodes=final["nodes"])
        out = (x, mse, avg, nodes, hops)
        self.keys[self.calls_made], self.outputs[self.calls_made] = key, out
        self.calls_made += 1
        return out

    def _host(self, i):
        return tuple(np.asarray(a) for a in self.outputs[i])

    def walk_stream(self, i):
        return np.asarray(self.outputs[i][3]), np.asarray(self.outputs[i][4])

    def next_first(self, i):
        """Each call starts from the node the previous call's last step
        reached, so that step is judged against it."""
        nxt = self.outputs.get(i + 1)
        return None if nxt is None else np.asarray(nxt[3])[:, 0]

    def failed_calls(self, calls) -> int:
        """Calls with a node outside [0, n), a hop count outside [1, r], or
        a loss or model that is not finite."""
        bad = 0
        for i in calls:
            x, mse, avg, nodes, hops = self._host(i)
            finite = all(np.isfinite(a).all() for a in (x, mse, avg))
            bad += int(self.bad_walk(nodes, hops) or not finite)
        return bad

    def trace_counts(self, calls) -> dict:
        n, dim = self.data.features.shape
        per_step = counts.fleet_step_flops(n, dim, self.walkers, self.avg_every)
        steps = self.steps * len(calls)
        return {"fleet_flops": per_step * steps, "fleet_steps": steps}

    def follow(self, dtype=jnp.float32):
        """The reference (or, with ``dtype=bfloat16``, the control) through
        the warm-up calls, on the program's own walk stream; the reference's
        per-walker losses at the precision the configuration states."""
        loss_inputs = self.loss_inputs if dtype == jnp.float32 else None
        x = jnp.zeros((self.walkers, self.data.features.shape[1]), jnp.float32)
        args = (jnp.asarray(self.data.features, jnp.float32),
                jnp.asarray(self.data.targets, jnp.float32), jnp.asarray(self.weights))
        out = []
        for i in range(self.warmup_calls):
            x, mse, avg = reference_call(x, jnp.asarray(self.outputs[i][3]), *args,
                                         self.gamma, avg_every=self.avg_every,
                                         dtype=dtype, block=LOSS_BLOCK,
                                         loss_inputs=loss_inputs)
            out.append(tuple(np.asarray(a) for a in (x, mse, avg)))
        return out

    @staticmethod
    def numbers(got, ref) -> dict:
        """The training numbers of ``got`` against ``ref``, each a list of
        (x, per-walker losses, averaged losses) per warm-up call."""

        def rel(a, b):
            return float(np.max(np.abs(a - b) / np.abs(b)))

        def worst_walker_norm_gap(xa, xb):
            na, nb = np.linalg.norm(xa, axis=1), np.linalg.norm(xb, axis=1)
            return float(np.max(np.abs(na - nb) / np.maximum(nb, np.median(nb))))

        return {
            "loss_gap": max(rel(g[2], r[2]) for g, r in zip(got, ref)),
            "walker_loss_gap": max(rel(g[1], r[1]) for g, r in zip(got, ref)),
            "first_update_gap": worst_walker_norm_gap(got[0][0], ref[0][0]),
            "change_gap": worst_walker_norm_gap(got[-1][0], ref[-1][0]),
        }

    def compare(self, calls, rng) -> dict:
        self.chosen = list(range(self.warmup_calls)) + self.sample(calls, rng)
        keep = set(self.chosen) | {i + 1 for i in self.chosen}
        self.outputs = {i: self._host(i) for i in keep if i in self.outputs}
        self.fleet = self.x = self.args = self.engine = None
        got = [self.outputs[i][:3] for i in range(self.warmup_calls)]
        numbers = {"walk_mismatch_share": self.judge_walk(self.chosen)}
        numbers.update(self.numbers(got, self.follow()))
        return numbers

    def control(self) -> dict:
        """The numbers of the bfloat16 reference put in the program's place:
        its walk steps from the compared calls' nodes, and its training
        along the warm-up calls' walk stream."""
        numbers = {"walk_mismatch_share": self.walk_control(self.chosen)}
        numbers.update(self.numbers(self.follow(jnp.bfloat16), self.follow()))
        return numbers


def build(spec: dict, seed: int) -> FleetOp:
    return FleetOp(spec, seed)
