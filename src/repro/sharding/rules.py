"""Logical-axis sharding rules -> PartitionSpecs for every param / cache /
batch leaf, with automatic divisibility fallback.

Scheme (DESIGN.md §5):

* ``fsdp_tp`` (training): 2-D parameter sharding — the "embed"-like axis over
  the ``data`` mesh axis (FSDP; XLA inserts all-gathers at use sites and
  reduce-scatters in the backward), the "parallel" axis (heads / mlp / vocab /
  expert) over ``model`` (tensor parallelism).  Optimizer state inherits the
  param specs (ZeRO-3-equivalent).  Params are replicated across ``pod``;
  the batch is sharded over (pod, data).
* ``tp_decode`` (serving): weight-stationary tensor parallelism — parallel
  axes over ``model``, embed axes replicated; KV caches shard batch over
  ``data`` and kv-heads over ``model`` (falling back to head_dim when the
  kv-head count does not divide the mesh axis — e.g. GQA kv=8 on model=16).
* ``fsdp_decode``: like tp_decode but embed axes also over ``data`` — used
  when weights alone exceed per-chip HBM under pure TP (jamba-398b).

A mesh axis is assigned to at most one tensor dim (PartitionSpec constraint);
rules list logical axes per trailing dim and the first divisible unclaimed
axis wins, others degrade to replication.  Leading stacked dims (layers /
periods / inner stacks) are auto-padded with None.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "PROFILES",
    "spec_for_leaf",
    "param_specs",
    "batch_specs",
    "cache_specs",
    "opt_state_specs",
    "named_shardings",
    "resolve_walker_axis",
    "walker_batch_specs",
    "fleet_specs",
]

# logical axes for the TRAILING dims of each known leaf name
PARAM_RULES: dict[str, tuple] = {
    "table": ("vocab", "embed"),
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "bq": ("heads", None),
    "bk": ("kv_heads", None),
    "bv": ("kv_heads", None),
    "w_gate": ("embed", "mlp"),  # rank-3 (expert) handled below
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    "w_in": ("embed", "mlp"),
    "w_out": ("mlp", "embed"),
    "b_in": ("mlp",),
    "b_out": (None,),
    "router": ("embed", None),
    "in_proj": ("embed", "mlp"),
    "out_proj": ("mlp", "embed"),
    "conv_w": (None, "mlp"),
    "conv_b": ("mlp",),
    "norm_scale": (None,),
    "a_log": (None,),
    "d_skip": (None,),
    "dt_bias": (None,),
    "scale": (None,),
    "bias": (None,),
    "dec_pos": (None, "embed"),
}

EXPERT_RULES: dict[str, tuple] = {
    "w_gate": ("expert", "embed", "mlp"),
    "w_up": ("expert", "embed", "mlp"),
    "w_down": ("expert", "mlp", "embed"),
}

CACHE_RULES: dict[str, tuple] = {
    "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "slot_pos": (None,),
    "conv": ("batch", None, "mlp"),
    "ssm": ("batch", "heads", None, None),
}

# logical -> mesh axis, per profile.  "batch" resolves to pod+data jointly.
# "walker" is the W-walker fleet axis of repro.walk_sgd.fleet: the leading
# dim of every walker-batch leaf (walk nodes, stacked per-walker model /
# optimizer / walk state) maps to the data mesh axis, so the periodic
# cross-walker model average lowers to an all-reduce along "data".
PROFILES: dict[str, dict] = {
    "fsdp_tp": {
        "embed": "data",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "batch": ("pod", "data"),
        "kv_seq": None,
        "walker": "data",
    },
    "tp_decode": {
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "batch": ("pod", "data"),
        "kv_seq": None,
        "walker": "data",
    },
    "fsdp_decode": {
        "embed": "data",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "batch": ("pod", "data"),
        "kv_seq": None,
        "walker": "data",
    },
    # pure walker-parallel fleet (regression path / engine sweeps): the
    # whole mesh is one walker axis, graph state replicated.
    "fleet": {
        "walker": "data",
    },
}


def _mesh_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _resolve_axis(logical, profile, mesh_sizes, dim_size, used):
    """Map one logical axis to a mesh axis (or None) respecting divisibility
    and single-use; supports tuple mesh axes (e.g. batch over (pod, data))."""
    if logical is None:
        return None
    target = profile.get(logical)
    if target is None:
        return None
    if isinstance(target, tuple):
        axes = tuple(a for a in target if a in mesh_sizes and a not in used)
        total = int(np.prod([mesh_sizes[a] for a in axes])) if axes else 1
        if axes and dim_size % total == 0 and dim_size > 0:
            used.update(axes)
            return axes if len(axes) > 1 else axes[0]
        # retry with progressively fewer axes (drop leading)
        for k in range(1, len(axes)):
            sub = axes[k:]
            total = int(np.prod([mesh_sizes[a] for a in sub]))
            if dim_size % total == 0 and dim_size > 0:
                used.update(sub)
                return sub if len(sub) > 1 else sub[0]
        return None
    if target not in mesh_sizes or target in used:
        return None
    if dim_size % mesh_sizes[target] != 0 or dim_size == 0:
        return None
    used.add(target)
    return target


def spec_for_leaf(
    name: str,
    shape: tuple,
    profile_name: str,
    mesh: Mesh,
    rules: Optional[dict] = None,
    is_expert: bool = False,
) -> P:
    """PartitionSpec for one leaf, padding leading stacked dims with None."""
    rules = rules or PARAM_RULES
    profile = PROFILES[profile_name]
    mesh_sizes = _mesh_sizes(mesh)
    logical = rules.get(name)
    if is_expert and name in EXPERT_RULES and len(shape) >= 3:
        # routed-expert weight: trailing (E, D, F) under optional stacked dims
        logical = EXPERT_RULES[name]
    if logical is None:
        return P()
    n_lead = len(shape) - len(logical)
    if n_lead < 0:  # rule longer than rank (e.g. scalar variants): replicate
        return P()
    used: set = set()
    entries = [None] * n_lead
    for logical_axis, dim in zip(logical, shape[n_lead:]):
        entries.append(_resolve_axis(logical_axis, profile, mesh_sizes, dim, used))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return str(entry.name)
    return ""


def _path_keys(path) -> tuple:
    return tuple(
        str(e.key) if isinstance(e, jax.tree_util.DictKey) else getattr(e, "name", "")
        for e in path
    )


def param_specs(params_shapes, profile_name: str, mesh: Mesh):
    """Spec tree matching a params (or eval_shape) tree."""

    def assign(path, leaf):
        keys = _path_keys(path)
        is_expert = "moe" in keys and "shared" not in keys
        return spec_for_leaf(
            _leaf_name(path), leaf.shape, profile_name, mesh, is_expert=is_expert
        )

    return jax.tree_util.tree_map_with_path(assign, params_shapes)


def cache_specs(cache_shapes, profile_name: str, mesh: Mesh):
    def assign(path, leaf):
        return spec_for_leaf(
            _leaf_name(path), leaf.shape, profile_name, mesh, rules=CACHE_RULES
        )

    return jax.tree_util.tree_map_with_path(assign, cache_shapes)


def batch_specs(batch_shapes, profile_name: str, mesh: Mesh):
    """Batch dict: dim0 = batch over (pod, data); everything else replicated."""
    profile = PROFILES[profile_name]
    mesh_sizes = _mesh_sizes(mesh)

    def assign(path, leaf):
        del path
        used: set = set()
        first = _resolve_axis("batch", profile, mesh_sizes, leaf.shape[0], used)
        return P(first, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(assign, batch_shapes)


def opt_state_specs(opt_state_shapes, p_specs, params_shapes, profile_name: str, mesh: Mesh):
    """Optimizer-state specs: state leaves matching a param shape inherit that
    param's spec; reduced-shape leaves (adafactor rows/cols) get a spec derived
    from the param rule re-applied to their own shape; scalars replicate."""
    flat_params = {
        tuple(str(k) for k in path): (leaf.shape, spec)
        for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(params_shapes)[0],
            jax.tree_util.tree_leaves(p_specs, is_leaf=lambda x: isinstance(x, P)),
        )
    }
    by_shape: dict = {}
    for shape, spec in flat_params.values():
        by_shape.setdefault(shape, spec)

    def assign(path, leaf):
        if leaf.shape == ():
            return P()
        if leaf.shape in by_shape:
            return by_shape[leaf.shape]
        # adafactor factored moments: re-derive from the leaf name fallback
        return spec_for_leaf(_leaf_name(path), leaf.shape, profile_name, mesh)

    return jax.tree_util.tree_map_with_path(assign, opt_state_shapes)


def named_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Walker-fleet specs (repro.walk_sgd.fleet): the "walker" logical axis.
# ---------------------------------------------------------------------------


def resolve_walker_axis(
    num_walks: int, mesh: Mesh, profile_name: str = "fleet"
) -> Optional[NamedSharding]:
    """NamedSharding for a 1-D ``(W,)`` walker-axis leaf, or ``None`` when
    the profile's walker mesh axis is absent or W does not divide it
    (replication fallback — same degradation rule as every other logical
    axis here)."""
    used: set = set()
    axis = _resolve_axis(
        "walker", PROFILES[profile_name], _mesh_sizes(mesh), num_walks, used
    )
    if axis is None:
        return None
    return NamedSharding(mesh, P(axis))


def walker_batch_specs(
    tree, num_walks: int, mesh: Mesh, profile_name: str = "fleet"
):
    """Spec tree for a walker-stacked pytree: every leaf whose leading dim
    equals ``num_walks`` gets the walker mesh axis on dim 0 (stacked
    per-walker params / optimizer state / walk state / ``x0s``); leaves
    without the walker batch dim — and everything when W does not divide
    the axis — replicate."""
    profile = PROFILES[profile_name]
    mesh_sizes = _mesh_sizes(mesh)

    def assign(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] == num_walks:
            used: set = set()
            axis = _resolve_axis("walker", profile, mesh_sizes, shape[0], used)
            if axis is not None:
                return P(axis, *([None] * (len(shape) - 1)))
        return P()

    return jax.tree_util.tree_map(assign, tree)


def fleet_specs(fleet, mesh: Mesh, profile_name: str = "fleet"):
    """Spec tree matching a ``repro.walk_sgd.fleet.WalkFleet``: the walk
    ``nodes`` ride the walker axis, every engine leaf (padded neighbor
    tables, ragged CSR ``indptr``/``indices`` row state, the flat per-edge
    ``edge_cdf`` and its ``cdf_tiles``) is **replicated** — walker
    positions are data-dependent gathers into the graph, so keeping graph
    state whole on every device avoids cross-device gathers on the walk's
    hot path."""
    import dataclasses

    wspec = walker_batch_specs(
        {"nodes": fleet.nodes}, fleet.num_walks, mesh, profile_name
    )["nodes"]
    engine_specs = jax.tree_util.tree_map(lambda _: P(), fleet.engine)
    return dataclasses.replace(fleet, engine=engine_specs, nodes=wspec)
