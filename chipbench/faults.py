"""Faults planted in the program under the benchmark, to show that the
checks of ``correct`` catch each fault that a cell can have.

Used by ``tests/chipbench`` at a small size on the CPU and by
``chipbench/calibrate.py --fault`` at the cell's size on the chip.  Each
plant patches the program for the duration of a ``with`` block and
clears JAX's caches on the way in and out, so that no program traced
before or after the block is reused.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

# the faults each kind of cell can have
CELL_FAULTS = {
    "walk": ("walk_unchanged", "walk_half", "walk_altered"),
    "fleet": ("fleet_unchanged", "fleet_half", "walk_altered"),
}


def _step_fault(kind):
    from repro.core import engine as engine_mod

    original = engine_mod.WalkEngine.step

    def step(self, key, nodes, *args, **kwargs):
        out = original(self, key, nodes, *args, **kwargs)
        nxt, hops = out[0], out[1]
        if kind == "walk_unchanged":  # the step returns its state unchanged
            nxt, hops = nodes, jnp.ones_like(hops)
        elif kind == "walk_half":  # half of the walkers are left out
            half = jnp.arange(nxt.shape[0]) < nxt.shape[0] // 2
            nxt = jnp.where(half, nxt, nodes)
            hops = jnp.where(half, hops, 1)
        else:  # walk_altered: every next node altered where it is produced
            nxt = (nxt + 1) % self.degrees.shape[0]
        return (nxt, hops) + tuple(out[2:])

    return engine_mod.WalkEngine, "step", original, step


def _fleet_fault(kind):
    from repro.walk_sgd import fleet as fleet_mod

    if kind == "fleet_half":  # the average is the mean over half the walkers
        original = fleet_mod.fleet_average

        def fleet_average(tree, do_avg=None):
            def avg(p):
                m = jnp.mean(p[: p.shape[0] // 2], axis=0, keepdims=True)
                m = jnp.broadcast_to(m, p.shape).astype(p.dtype)
                return m if do_avg is None else jnp.where(do_avg, m, p)

            return jax.tree_util.tree_map(avg, tree)

        return fleet_mod, "fleet_average", original, fleet_average

    original = fleet_mod.run_fleet  # fleet_unchanged: models returned as given

    def run_fleet(key, x0s, *args, **kwargs):
        out = original(key, x0s, *args, **kwargs)
        return (x0s,) + tuple(out[1:])

    return fleet_mod, "run_fleet", original, run_fleet


@contextlib.contextmanager
def planted(kind: str):
    if kind.startswith("walk_"):
        owner, attr, original, broken = _step_fault(kind)
    elif kind in ("fleet_unchanged", "fleet_half"):
        owner, attr, original, broken = _fleet_fault(kind)
    else:
        raise ValueError(f"unknown fault {kind!r}")
    jax.clear_caches()
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, original)
        jax.clear_caches()
