"""One fleet loop: the W-walker batch behind every walk-SGD training path.

The repo used to carry three divergent walk-SGD loops — the single-walk
``trainer._run_scan``, the batched ``trainer._run_scan_multi`` and the
LLM orchestrator's ``WalkContext.advance``/``make_train_step`` step — none
of which touched the mesh/sharding stack.  This module collapses them into
one **fleet** abstraction: the W walker batch (walk nodes, per-walker
model/optimizer state, per-walker PRNG streams on the LLM path) is one
pytree whose walker-batch leaves carry a leading ``(W, ...)`` axis, the
``walker`` logical axis of ``repro.sharding.rules``.  Sharded over the
mesh ``data`` axis (``repro.sharding.rules.fleet_specs`` /
``repro.launch.mesh.make_walker_mesh``) the fleet trains W walks across
devices off ONE batched :class:`~repro.core.engine.WalkEngine` transition
per step, with the graph state — padded tables, ragged CSR row state,
the flat per-edge CDF — **replicated** (walk positions are data-dependent
gathers into the graph; replication keeps them local).

Periodic cross-walker model averaging (``avg_every``-style local SGD) is
:func:`fleet_average`: a mean over the leading walker axis, which XLA
lowers to an all-reduce along the mesh axis the walker axis is sharded
over — so the only cross-device traffic of the fleet is one model-sized
collective every ``avg_every`` steps
(``repro.walk_sgd.comm_model.fleet_averaging_traffic`` prices it).

This is the multi-walker regime of the journal extension *Decentralized
Learning via Random Walk with Jumps* (arXiv:2604.12260): W independent
MHLJ walks over the same graph, each carrying its own model, periodically
averaged.  Averaging divides the Markov-sampling variance term of
Theorem 1 by ~W while the O(p_J^2) perturbation bias is unchanged — the
convergence-vs-num-walkers sweep in ``benchmarks/multi_walk.py`` /
``benchmarks/large_graph_walk.py`` measures exactly that.

Consumers (all three former loops route through here):

* ``repro.walk_sgd.trainer.run_rw_sgd`` — the W=1 case of
  :func:`run_fleet` (bitwise-identical per key to the pre-refactor
  single-walk scan; ``tests/test_fleet.py`` pins it against a frozen
  oracle copy).
* ``repro.walk_sgd.trainer.run_rw_sgd_multi`` — constructs a
  :class:`WalkFleet` and calls :func:`run_fleet`, optionally under a
  mesh.
* ``repro.walk_sgd.llm_trainer`` / ``repro.walk_sgd.multi_walk`` — thin
  consumers: ``WalkContext.advance`` advances a one-walker fleet, and
  :func:`make_fleet_step` is THE W-walker LLM step
  (``make_multi_walk_step`` delegates here).
* ``repro.launch.serve.ServeSimulator`` — the fleet as a *service
  fabric*: W walkers route serving requests pinned to graph nodes (one
  batched :meth:`WalkFleet.advance` per tick; more walkers = more pickup
  bandwidth), the non-training consumer of the walker batch — see
  docs/serving.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import WalkEngine
from repro.models import regression as reg
from repro.sharding.rules import (
    fleet_specs,
    named_shardings,
    resolve_walker_axis,
    walker_batch_specs,
)

__all__ = [
    "WalkFleet",
    "sample_initial_nodes",
    "migrate_walk_nodes",
    "fleet_average",
    "run_fleet",
    "shard_fleet",
    "shard_walker_batch",
    "make_fleet_step",
    "init_fleet_walk_state",
    "save_fleet_checkpoint",
    "load_fleet_checkpoint",
]


def sample_initial_nodes(
    n: int,
    num_walks: int,
    *,
    seed: int = 0,
    v0s: Optional[Sequence[int]] = None,
) -> np.ndarray:
    """THE initial-node seeding + validation for every multi-walk path.

    ``v0s=None`` samples ``num_walks`` start nodes with
    ``np.random.default_rng(seed)`` (without replacement while the fleet
    fits the graph, with replacement beyond) — the exact stream the
    pre-fleet ``run_rw_sgd_multi`` and ``init_multi_walk_state`` each
    duplicated, now in one place so the regression and LLM paths sample
    identical fleets for the same seed.  Explicit ``v0s`` are validated
    (shape ``(num_walks,)``, every node in ``[0, n)``).
    """
    if n <= 0:
        # total node failure or full-departure churn: say WHY seeding is
        # impossible instead of letting rng.choice/indexing fail opaquely
        raise ValueError(
            f"cannot seed {num_walks} walks: the active-node set is empty "
            f"(n={n}) — a graph with no live/in-graph nodes cannot host a "
            "fleet (total failure, or every node departed in a churn)"
        )
    if v0s is None:
        rng = np.random.default_rng(seed)
        v0s = rng.choice(n, size=num_walks, replace=num_walks > n)
    v0s = np.asarray(v0s, np.int32)
    if v0s.shape != (num_walks,):
        raise ValueError(f"v0s must have shape ({num_walks},), got {v0s.shape}")
    if v0s.size and (int(v0s.min()) < 0 or int(v0s.max()) >= n):
        raise ValueError(
            f"v0s must be node ids in [0, {n}), got range "
            f"[{int(v0s.min())}, {int(v0s.max())}]"
        )
    return v0s


def migrate_walk_nodes(
    nodes,
    new_degrees,
    *,
    seed: int = 0,
):
    """THE walk-continuity rule across graph versions — see
    docs/dynamic_graphs.md.

    After an edge churn (``graphs.apply_edge_churn``), a walk standing on
    a node that is still *in* the new graph (degree > 1, i.e. any edge
    beyond the structural self-loop) carries its position unchanged —
    bitwise, no re-draw.  A walk standing on a **departed** node (degree
    exactly 1: self-loop only, unreachable for every other walk) is
    re-seeded through the existing :func:`sample_initial_nodes` stream
    over the surviving nodes: draw index ``w``'s node is
    ``active[sample_initial_nodes(len(active), W, seed=seed)[w]]`` with
    ``active`` the ascending in-graph node ids — documented here because
    the continuity test pins exactly this formula.  RNG continuity for
    surviving walks is free by construction: the fleet loops split one
    key stream over all W walks regardless of position, so carrying a
    position carries its uniform stream.

    Returns ``(new_nodes, displaced)``: the ``(W,)`` int32 positions and
    the boolean mask of re-seeded walks.
    """
    nodes_np = np.atleast_1d(np.asarray(nodes, np.int32))
    deg = np.asarray(new_degrees, np.int64)
    in_graph = deg > 1
    if not in_graph.any():
        raise ValueError(
            "no node of the churned graph has a non-loop edge; every walk "
            "would be displaced with nowhere to land"
        )
    if nodes_np.size and (
        int(nodes_np.min()) < 0 or int(nodes_np.max()) >= deg.size
    ):
        raise ValueError("walk positions out of range for the churned graph")
    displaced = ~in_graph[nodes_np]
    new_nodes = nodes_np.copy()
    if displaced.any():
        active = np.nonzero(in_graph)[0].astype(np.int32)
        draws = sample_initial_nodes(
            int(active.size), int(nodes_np.size), seed=seed
        )
        new_nodes[displaced] = active[draws[displaced]]
    return new_nodes, displaced


def fleet_average(tree, do_avg=None):
    """Cross-walker model average — THE ``avg_every`` collective.

    Every leaf is averaged over its leading walker axis and re-broadcast
    to all W walkers.  When the walker axis is sharded over a mesh axis
    (``repro.sharding.rules.fleet_specs``), XLA lowers the mean to an
    all-reduce along that axis — one model-sized collective, independent
    of W (each device contributes its local partial mean; see
    ``repro.walk_sgd.comm_model.fleet_averaging_traffic``).

    ``do_avg=None`` averages unconditionally; a traced boolean makes the
    average conditional per step (the ``(t + 1) % avg_every == 0`` gate of
    the fleet loops) while keeping shapes static.
    """

    def avg(p):
        m = jnp.broadcast_to(
            jnp.mean(p, axis=0, keepdims=True), p.shape
        ).astype(p.dtype)
        return m if do_avg is None else jnp.where(do_avg, m, p)

    return jax.tree_util.tree_map(avg, tree)


@dataclasses.dataclass(frozen=True, eq=False)
class WalkFleet:
    """W parallel walkers riding one batched engine — THE walker batch.

    ``nodes`` is the ``(W,)`` walk-position vector (a scalar for the
    one-walker LLM adapter, which keeps the engine's squeeze semantics),
    the ``walker`` logical axis of ``repro.sharding.rules``; ``engine``
    holds the replicated graph/row state.  Registered as a pytree
    (``engine``/``nodes`` are children, ``num_walks``/``avg_every`` ride
    as static aux data) so a fleet crosses ``jax.jit`` boundaries as a
    plain argument exactly like the engine itself does.
    """

    engine: WalkEngine
    nodes: jnp.ndarray  # (W,) int32 walk positions (scalar for W=1 adapter)
    num_walks: int = 1  # static
    avg_every: int = 0  # static: 0 = never average

    @classmethod
    def create(
        cls,
        engine: WalkEngine,
        num_walks: int,
        *,
        v0s: Optional[Sequence[int]] = None,
        seed: int = 0,
        avg_every: int = 0,
    ) -> "WalkFleet":
        """Fleet with :func:`sample_initial_nodes` seeding/validation."""
        n = int(engine.degrees.shape[0])
        v0 = sample_initial_nodes(n, num_walks, seed=seed, v0s=v0s)
        return cls(
            engine=engine,
            nodes=jnp.asarray(v0),
            num_walks=num_walks,
            avg_every=avg_every,
        )

    def migrate(self, engine: WalkEngine, *, seed: int = 0):
        """Carry this fleet onto a churned engine (next graph version).

        Applies :func:`migrate_walk_nodes` to the walk positions against
        the new engine's degree vector: surviving walks keep their
        position bitwise, walks on departed nodes re-seed via the
        documented :func:`sample_initial_nodes` path.  Returns
        ``(new_fleet, displaced)``; the scalar-``nodes`` W=1 adapter shape
        is preserved.
        """
        was_scalar = jnp.ndim(self.nodes) == 0
        new_nodes, displaced = migrate_walk_nodes(
            self.nodes, np.asarray(engine.degrees), seed=seed
        )
        nodes = jnp.asarray(
            new_nodes[0] if was_scalar else new_nodes, jnp.int32
        )
        return dataclasses.replace(self, engine=engine, nodes=nodes), displaced

    def advance(
        self,
        key: jax.Array,
        *,
        p_j=None,
        lipschitz: Optional[jnp.ndarray] = None,
        faults=None,
    ):
        """ONE batched MHLJ transition for all W walkers.

        Returns ``(advanced_fleet, hops)``; ``hops`` is the Remark-1
        physical transition count per walker.  With
        ``faults=(FaultModel, FaultState)`` the transition is
        liveness-masked (docs/faults.md) and a third element carries the
        engine's fault aux (``blocked_steps`` — the caller's next
        ``FaultState.blocked`` — plus the ``fault_blocked``/``rescued``
        telemetry masks); ``faults=None`` is bitwise the pre-fault
        advance.
        """
        if faults is None:
            nxt, hops = self.engine.step(
                key, self.nodes, p_j=p_j, lipschitz=lipschitz
            )
            return dataclasses.replace(self, nodes=nxt), hops
        nxt, hops, aux = self.engine.step(
            key, self.nodes, p_j=p_j, lipschitz=lipschitz,
            with_aux=True, faults=faults,
        )
        return dataclasses.replace(self, nodes=nxt), hops, aux


    # -- crash consistency (docs/faults.md: "checkpoint format") ------------
    def checkpoint(self) -> dict:
        """Host-side snapshot: pytree → flat numpy arrays + static aux.

        Every engine data field becomes a plain ``np.ndarray`` (tuples of
        arrays, e.g. the bucketed ladder, stay tuples of arrays), engine
        statics ride in ``engine_meta`` and fleet statics at the top
        level.  ``walker_sharding`` is deliberately dropped — device
        placement is not state; re-place with :func:`shard_fleet` after
        :meth:`restore`.  :meth:`restore` of this dict resumes bitwise
        (``tests/test_faults.py`` pins a mid-run kill-and-restore).
        """
        from repro.core.engine import (
            _ENGINE_DATA_FIELDS,
            _ENGINE_META_FIELDS,
        )

        data = {}
        for f in _ENGINE_DATA_FIELDS:
            v = getattr(self.engine, f)
            if v is None:
                data[f] = None
            elif isinstance(v, tuple):
                data[f] = tuple(np.asarray(x) for x in v)
            else:
                data[f] = np.asarray(v)
        meta = {
            f: getattr(self.engine, f)
            for f in _ENGINE_META_FIELDS
            if f != "walker_sharding"
        }
        meta["walker_sharding"] = None
        # a python-float p_j is a static-style scalar; keep it one across
        # the round trip so the restored pytree has the same leaf set
        if isinstance(self.engine.p_j, float):
            data["p_j"] = float(self.engine.p_j)
        return {
            "version": 1,
            "num_walks": self.num_walks,
            "avg_every": self.avg_every,
            "nodes": np.asarray(self.nodes),
            "engine_data": data,
            "engine_meta": meta,
        }

    @classmethod
    def restore(cls, ckpt: dict) -> "WalkFleet":
        """Rebuild a fleet from :meth:`checkpoint` output — bitwise."""
        from repro.core.engine import WalkEngine as _Engine

        data = {}
        for f, v in ckpt["engine_data"].items():
            if v is None or isinstance(v, float):
                data[f] = v
            elif isinstance(v, tuple):
                data[f] = tuple(jnp.asarray(x) for x in v)
            else:
                data[f] = jnp.asarray(v)
        engine = _Engine(**data, **ckpt["engine_meta"])
        return cls(
            engine=engine,
            nodes=jnp.asarray(ckpt["nodes"]),
            num_walks=ckpt["num_walks"],
            avg_every=ckpt["avg_every"],
        )


def save_fleet_checkpoint(
    path: str,
    fleet: WalkFleet,
    *,
    step: int = 0,
    extras: Optional[dict] = None,
) -> str:
    """Crash-consistent fleet checkpoint on disk (atomic ``os.replace``).

    One ``.npz`` holding the :meth:`WalkFleet.checkpoint` arrays plus any
    ``extras`` arrays (per-walker models, a ``FaultState``'s leaves, the
    DADA round index — whatever the caller's loop carries), and a JSON
    sidecar entry for the static aux.  A crash mid-write never corrupts
    an existing checkpoint: the temp file is renamed into place only
    after a full flush.
    """
    import json
    import os
    import tempfile

    ckpt = fleet.checkpoint()
    arrays: dict = {"nodes": ckpt["nodes"]}
    none_fields, tuple_lens, scalar_fields = [], {}, {}
    for f, v in ckpt["engine_data"].items():
        if v is None:
            none_fields.append(f)
        elif isinstance(v, float):
            scalar_fields[f] = v
        elif isinstance(v, tuple):
            tuple_lens[f] = len(v)
            for i, x in enumerate(v):
                arrays[f"engine_data/{f}/{i}"] = x
        else:
            arrays[f"engine_data/{f}"] = v
    extras = extras or {}
    for name, x in extras.items():
        arrays[f"extras/{name}"] = np.asarray(x)
    meta = {
        "version": ckpt["version"],
        "num_walks": ckpt["num_walks"],
        "avg_every": ckpt["avg_every"],
        "step": int(step),
        "engine_meta": {
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in ckpt["engine_meta"].items()
        },
        "meta_tuples": [
            k for k, v in ckpt["engine_meta"].items() if isinstance(v, tuple)
        ],
        "none_fields": none_fields,
        "tuple_lens": tuple_lens,
        "scalar_fields": scalar_fields,
        "extras": sorted(extras),
    }
    arrays["meta_json"] = np.asarray(json.dumps(meta))
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            np.savez(fh, **arrays)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return path


def load_fleet_checkpoint(path: str):
    """Load :func:`save_fleet_checkpoint` → ``(fleet, step, extras)``."""
    import json

    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta_json"]))
        data: dict = {f: None for f in meta["none_fields"]}
        data.update(meta["scalar_fields"])
        for f, k in meta["tuple_lens"].items():
            data[f] = tuple(z[f"engine_data/{f}/{i}"] for i in range(k))
        for key in z.files:
            if key.startswith("engine_data/") and key.count("/") == 1:
                data[key.split("/", 1)[1]] = z[key]
        engine_meta = {
            k: (tuple(v) if k in meta["meta_tuples"] and v is not None else v)
            for k, v in meta["engine_meta"].items()
        }
        fleet = WalkFleet.restore(
            {
                "version": meta["version"],
                "num_walks": meta["num_walks"],
                "avg_every": meta["avg_every"],
                "nodes": z["nodes"],
                "engine_data": data,
                "engine_meta": engine_meta,
            }
        )
        extras = {name: z[f"extras/{name}"] for name in meta["extras"]}
    return fleet, meta["step"], extras


def _fleet_flatten(f: WalkFleet):
    return (f.engine, f.nodes), (f.num_walks, f.avg_every)


def _fleet_unflatten(aux, children) -> WalkFleet:
    engine, nodes = children
    num_walks, avg_every = aux
    return WalkFleet(
        engine=engine, nodes=nodes, num_walks=num_walks, avg_every=avg_every
    )


jax.tree_util.register_pytree_node(WalkFleet, _fleet_flatten, _fleet_unflatten)


# Device scopes of the fleet step (``jax.named_scope``: HLO metadata only),
# beside the walk's own ``engine.WALK_TRANSITION_SCOPE``: the per-walker SGD
# update with its row gathers, the model average, and the loss evaluation.
FLEET_SGD_SCOPE = "fleet_sgd"
FLEET_AVERAGE_SCOPE = "fleet_average"
FLEET_LOSS_EVAL_SCOPE = "fleet_loss_eval"


# ---------------------------------------------------------------------------
# THE fleet training scan (regression path): the single implementation that
# replaced trainer._run_scan (its W=1 case) and trainer._run_scan_multi.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_steps", "use_weights", "loss_grad", "start_step", "total_steps",
    ),
)
def _fleet_scan(
    key,
    x0s,  # (W, dim) per-walker models
    features,
    targets,
    weights,  # (n,) L_bar / L_v (ones when unweighted)
    fleet: WalkFleet,  # pytree arg: arrays traced, W/avg_every/layout static
    num_steps: int,
    gamma: float,
    p_j_sched,  # (num_steps,)
    use_weights: bool,
    loss_grad,  # static callable: grad of per-node loss
    faults=None,  # (FaultModel, FaultState) or None — docs/faults.md
    start_step: int = 0,  # static: absolute index of the first step taken
    total_steps=None,  # static: absolute run length the key stream is cut
    #   from — split(key, total)[start : start + num] so a resumed window
    #   replays the exact keys of the uninterrupted run (bitwise)
):
    engine = fleet.engine
    avg_every = fleet.avg_every
    grad_w = jax.vmap(loss_grad, in_axes=(0, 0, 0))
    fmodel = faults[0] if faults is not None else None

    def step(carry, inputs):
        if faults is None:
            xs, vs, t = carry
            key_t, p_j_t = inputs
            alive_w = None
        else:
            # fault timeline per tick: the fault process advances first
            # (nodes crash/recover), THEN the walkers react — a walker on
            # a dead node computes no update (its compute is down), takes
            # no part in averaging, and its handoff is liveness-rejected.
            xs, vs, t, fstate = carry
            key_t, p_j_t = inputs
            key_t, key_f = jax.random.split(key_t)
            fstate = fmodel.advance(key_f, fstate)
            alive_w = fmodel.live_mask(fstate)[vs]  # (W,) walker liveness
        with jax.named_scope(FLEET_SGD_SCOPE):
            gs = grad_w(xs, features[vs], targets[vs])  # (W, dim)
            ws = jnp.where(use_weights, weights[vs], 1.0)[:, None]
            xs_new = xs - gamma * ws * gs
            if alive_w is not None:
                xs_new = jnp.where(alive_w[:, None], xs_new, xs)
        if avg_every > 0:
            with jax.named_scope(FLEET_AVERAGE_SCOPE):
                do_avg = (t + 1) % avg_every == 0
                if alive_w is None:
                    xs_new = fleet_average(xs_new, do_avg)
                else:
                    # dead walkers are unreachable: they neither contribute to
                    # nor receive the average (a parked model stays frozen and
                    # drags the fleet only when it REJOINS — the stalled-worker
                    # cost benchmarks/fault_sweep.py measures)
                    w_live = alive_w.astype(xs_new.dtype)[:, None]
                    mean = (xs_new * w_live).sum(axis=0, keepdims=True) / (
                        jnp.maximum(w_live.sum(), 1.0)
                    )
                    avg = jnp.broadcast_to(mean, xs_new.shape).astype(
                        xs_new.dtype
                    )
                    xs_new = jnp.where(
                        do_avg & alive_w[:, None], avg, xs_new
                    )
        if faults is None:
            vs_next, hops = engine.step(key_t, vs, p_j=p_j_t)  # ONE batched call
        else:
            vs_next, hops, aux = engine.step(
                key_t, vs, p_j=p_j_t, with_aux=True, faults=(fmodel, fstate)
            )
            fstate = dataclasses.replace(
                fstate, blocked=aux["blocked_steps"]
            )
        with jax.named_scope(FLEET_LOSS_EVAL_SCOPE):
            mses = jax.vmap(reg.mse_objective, in_axes=(0, None, None))(
                xs_new, features, targets
            )
            avg_mse = reg.mse_objective(xs_new.mean(axis=0), features, targets)
        if faults is None:
            return (xs_new, vs_next, t + 1), (mses, avg_mse, vs, hops)
        return (
            (xs_new, vs_next, t + 1, fstate),
            (
                mses, avg_mse, vs, hops,
                aux["rescued"].sum(), aux["fault_blocked"].sum(),
            ),
        )

    total = num_steps if total_steps is None else total_steps
    keys = jax.random.split(key, total)[start_step:start_step + num_steps]
    t0 = jnp.int32(start_step)
    if faults is None:
        (xs_fin, vs_fin, _), (mses, avg_mses, nodes, hops) = jax.lax.scan(
            step, (x0s, fleet.nodes, t0), (keys, p_j_sched)
        )
        final = {"nodes": vs_fin, "fault_state": None, "rescued": None,
                 "blocked": None}
    else:
        (xs_fin, vs_fin, _, fstate_fin), (
            mses, avg_mses, nodes, hops, rescued, blocked
        ) = jax.lax.scan(
            step, (x0s, fleet.nodes, t0, faults[1]), (keys, p_j_sched)
        )
        final = {"nodes": vs_fin, "fault_state": fstate_fin,
                 "rescued": rescued, "blocked": blocked}
    with jax.named_scope(FLEET_LOSS_EVAL_SCOPE):
        mse0 = jax.vmap(reg.mse_objective, in_axes=(0, None, None))(
            x0s, features, targets
        )
        avg0 = reg.mse_objective(x0s.mean(axis=0), features, targets)
    return (
        xs_fin,
        jnp.concatenate([mse0[None], mses]).T,  # (W, T+1)
        jnp.concatenate([avg0[None], avg_mses]),  # (T+1,)
        nodes.T,  # (W, T) node holding the model at update t
        hops.T,  # (W, T)
        final,  # final walk positions + fault carry/telemetry (resume seam)
    )


def shard_fleet(fleet: WalkFleet, mesh) -> WalkFleet:
    """Place a fleet on ``mesh``: walker-axis leaves sharded, engine
    replicated, and the engine made shard-aware.

    The fleet's ``nodes`` get the ``walker`` logical axis's mesh axis
    (``repro.sharding.rules.fleet_specs``; replication fallback when W
    does not divide the axis), every engine leaf — padded tables, ragged
    CSR state, the flat per-edge CDF — is replicated, and the engine is
    handed the walker ``NamedSharding`` so its ``step``/``run`` keep the
    per-walk uniforms and outputs partitioned over the walker axis
    (:meth:`repro.core.engine.WalkEngine.with_walker_sharding`).
    """
    specs = fleet_specs(fleet, mesh)
    fleet = jax.device_put(fleet, named_shardings(specs, mesh))
    walker_sharding = resolve_walker_axis(fleet.num_walks, mesh)
    if walker_sharding is not None:
        fleet = dataclasses.replace(
            fleet, engine=fleet.engine.with_walker_sharding(walker_sharding)
        )
    return fleet


def shard_walker_batch(tree, num_walks: int, mesh):
    """Place a walker-stacked pytree (leading ``(W, ...)`` leaves — stacked
    params/opt/walk state on the LLM path, ``x0s`` on the regression path)
    per ``repro.sharding.rules.walker_batch_specs``."""
    specs = walker_batch_specs(tree, num_walks, mesh)
    return jax.device_put(tree, named_shardings(specs, mesh))


def run_fleet(
    key: jax.Array,
    x0s: jnp.ndarray,  # (W, dim)
    features: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    fleet: WalkFleet,
    num_steps: int,
    gamma: float,
    p_j_sched: jnp.ndarray,
    use_weights: bool,
    loss_grad: Callable,
    *,
    mesh=None,
    faults=None,
    fault_state=None,
    start_step: int = 0,
    total_steps: Optional[int] = None,
):
    """Run the fleet training scan, optionally mesh-sharded.

    With ``mesh``, the walker batch (``x0s`` and the fleet's nodes) is
    sharded over the ``walker`` logical axis, graph/data state is
    replicated, and the scan's periodic :func:`fleet_average` lowers to an
    all-reduce along the walker mesh axis.  Without a mesh this is exactly
    the pre-fleet single-device scan — bitwise-identical per key
    (``tests/test_fleet.py`` pins both paths against the frozen
    pre-refactor oracle).

    ``faults`` takes a :class:`repro.core.faults.FaultModel` for the
    liveness-masked regime (docs/faults.md): nodes crash/recover per
    tick, dead walkers stop updating/averaging, blocked walkers past the
    model's patience take the forced live-restricted jump.
    ``fault_state`` resumes a recorded :class:`FaultState` (defaults to
    the all-live state at tick ``start_step``).

    ``start_step``/``total_steps`` are the crash-recovery seam: the scan
    burns ``split(key, total_steps)[start_step : start_step+num_steps]``,
    so running ``[0, k)`` — checkpointing via
    :func:`save_fleet_checkpoint` — then ``[k, T)`` replays the exact
    per-step keys of the uninterrupted ``[0, T)`` run (bitwise; pinned by
    ``tests/test_faults.py``).  Pass the matching ``p_j_sched`` window
    (``full_sched[start_step:start_step+num_steps]``).

    Returns ``(x_final (W, dim), mse (W, T+1), avg_mse (T+1,),
    update_nodes (W, T), hops (W, T), final)`` where ``final`` carries
    the resume state: ``final["nodes"]`` are the walk positions after the
    last step and, under faults, ``final["fault_state"]`` plus per-step
    ``final["rescued"]``/``final["blocked"]`` (T,) totals.
    """
    if start_step < 0:
        raise ValueError(f"start_step must be >= 0, got {start_step}")
    total = num_steps if total_steps is None else total_steps
    if start_step + num_steps > total:
        raise ValueError(
            f"window [{start_step}, {start_step + num_steps}) exceeds "
            f"total_steps={total}"
        )
    faults_arg = None
    if faults is not None:
        n = int(fleet.engine.degrees.shape[0])
        w = int(jnp.atleast_1d(fleet.nodes).shape[0])
        if fault_state is None:
            fault_state = faults.init_state(n, w)
            if start_step:
                fault_state = dataclasses.replace(
                    fault_state, t=jnp.int32(start_step)
                )
        faults_arg = (faults, fault_state)
    if mesh is not None:
        fleet = shard_fleet(fleet, mesh)
        x0s = shard_walker_batch(x0s, fleet.num_walks, mesh)
        repl = named_shardings(
            jax.tree_util.tree_map(lambda _: jax.sharding.PartitionSpec(),
                                   (features, targets, weights, p_j_sched)),
            mesh,
        )
        features, targets, weights, p_j_sched = jax.device_put(
            (features, targets, weights, p_j_sched), repl
        )
    return _fleet_scan(
        key,
        x0s,
        features,
        targets,
        weights,
        fleet,
        num_steps,
        gamma,
        p_j_sched,
        use_weights,
        loss_grad,
        faults_arg,
        start_step=start_step,
        total_steps=total_steps,
    )


# ---------------------------------------------------------------------------
# THE fleet step for the LLM path (pjit-sharded models): vmapped per-walker
# update + one batched walk advance + the periodic averaging collective.
# ---------------------------------------------------------------------------


def make_fleet_step(model, optimizer, walk, avg_every: int = 0) -> Callable:
    """Jittable ``(params_w, opt_w, walk_w, batches_w, step_idx)`` fleet
    step for the large-architecture path.

    Each leaf of ``params_w``/``opt_w``/``walk_w``/``batches_w`` carries a
    leading walker axis (shard with :func:`shard_walker_batch`).  The
    single-walker train step (``repro.walk_sgd.llm_trainer``'s update
    body, walk advance disabled) is vmapped over walkers, all W walk
    positions advance through ONE batched engine transition
    (``walk.advance_batched`` → :meth:`WalkFleet.advance`), and
    ``avg_every > 0`` applies :func:`fleet_average` every that many steps.
    ``multi_walk.make_multi_walk_step`` is a thin alias of this.
    """
    from repro.walk_sgd.llm_trainer import make_train_step

    single = make_train_step(model, optimizer, walk, advance_walk=False)
    vstep = jax.vmap(single)

    def fleet_step(params_w, opt_w, walk_w, batches_w, step_idx):
        params_w, opt_w, walk_w, metrics = vstep(
            params_w, opt_w, walk_w, batches_w
        )
        walk_w = walk.advance_batched(walk_w)
        if avg_every > 0:
            do_avg = (step_idx + 1) % avg_every == 0
            params_w = fleet_average(params_w, do_avg)
        return params_w, opt_w, walk_w, metrics

    return fleet_step


def init_fleet_walk_state(
    n_nodes: int,
    num_walks: int,
    lipschitz: Optional[np.ndarray] = None,
    v0s: Optional[Sequence[int]] = None,
    seed: int = 0,
    online: bool = False,
):
    """Stacked LLM walk states for a W-walker fleet.

    Start nodes come from :func:`sample_initial_nodes` (the same
    seeding/validation the regression fleet constructor uses, so both
    paths sample identical fleets per seed); each walker gets its own
    PRNG stream (``seed * 1009 + i``).  Every leaf carries a leading
    walker axis — shard with :func:`shard_walker_batch`.
    """
    from repro.walk_sgd.llm_trainer import init_walk_state

    v0s = sample_initial_nodes(n_nodes, num_walks, seed=seed, v0s=v0s)
    states = [
        init_walk_state(
            n_nodes, lipschitz, v0=int(v), seed=seed * 1009 + i, online=online
        )
        for i, v in enumerate(v0s)
    ]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
