"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib and compiles for a ``v5e:2x2`` topology
description, so what Mosaic or XLA:TPU would refuse on the chip (tiling,
SMEM/VMEM capacity, partitioning) fails here at no chip time.  Nothing
runs: these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.  All rehearsals stay in this one file so one worker owns them.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.engine import (
    TPU_PALLAS_LAYOUTS,
    WALK_TRANSITION_SCOPE,
    WalkEngine,
    edge_cdf_tiles,
    span_event,
)
from repro.core.graphs import barabasi_albert
from repro.core.transition import MHLJParams

# barabasi_albert(100_000, 3, seed=0, layout="ragged"): the repo's
# full-scale graph (benchmarks/large_graph_walk.py, serve_throughput.py)
BA_N, BA_NNZ, BA_MAX_DEG = 100_000, 699_832, 1196
# chipbench's kron16-mhlj (Graph500 Kronecker, scale 16, largest component)
# and grid25x40-mhlj graphs: n, CSR entries with self-loops, max degree
KRON16 = (46_756, 1_865_304, 9810)
GRID25X40 = (1000, 4870, 5)
W = 8192
R = 3
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ragged_shapes(sharding, graph=(BA_N, BA_NNZ, BA_MAX_DEG)):
    """Shapes of a ragged engine's graph state: CSR, flat CDF and the CDF
    tile tables (``edge_cdf_tiles``) for ``graph = (n, nnz, max_deg)``."""
    n, nnz, max_deg = graph

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    tiles = jax.eval_shape(
        functools.partial(edge_cdf_tiles, max_degree=max_deg),
        s((nnz,), jnp.float32),
    )
    return dict(
        indptr=s((n + 1,), jnp.int32),
        indices=s((nnz,), jnp.int32),
        edge_cdf=s((nnz,), jnp.float32),
        cdf_tiles=tuple(s(t.shape, t.dtype) for t in tiles),
        degrees=s((n,), jnp.int32),
    )


def _ragged_engine(g, max_degree=BA_MAX_DEG, **kw):
    return WalkEngine(
        neighbors=None, degrees=g["degrees"], p_j=0.1, p_d=0.5, r=R,
        layout="ragged", indptr=g["indptr"], indices=g["indices"],
        edge_cdf=g["edge_cdf"], cdf_tiles=g["cdf_tiles"],
        max_degree=max_degree, cdf_width=max_degree, **kw,
    )


def test_ragged_kernel_compiles_at_ba100k(one_chip):
    from repro.kernels.walk_transition.kernel import walk_transition_ragged

    g = _ragged_shapes(one_chip)
    nodes = jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((W, 3 + R), jnp.float32, sharding=one_chip)

    def step(nodes, indptr, indices, edge_cdf, u):
        return walk_transition_ragged(
            nodes, indptr, indices, edge_cdf, u, p_d=0.5, r=R, interpret=False
        )

    compiled = jax.jit(step).lower(
        nodes, g["indptr"], g["indices"], g["edge_cdf"], u
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_engine_step_compiles(one_chip):
    """The jitted WalkEngine.step on the ragged layout, compiled kernel."""
    g = _ragged_shapes(one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    nodes = jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one_chip)

    def step(g, key, nodes):
        eng = _ragged_engine(g, backend="pallas", interpret=False)
        return eng.step(key, nodes)

    compiled = jax.jit(step).lower(g, key, nodes).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


def test_auto_ragged_engine_step_compiles_without_the_kernel(one_chip, monkeypatch):
    """The jitted WalkEngine.step under "auto" on a TPU: the ragged
    layout's W-wide XLA gathers, no Pallas kernel."""
    g = _ragged_shapes(one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    nodes = jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one_chip)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def step(g, key, nodes):
        eng = _ragged_engine(g)
        assert eng.resolved_backend == "scan"
        return eng.step(key, nodes)

    compiled = jax.jit(step).lower(g, key, nodes).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def _auto_ragged_step(sharding, graph, monkeypatch):
    """The jitted "auto" ragged step on a TPU for ``graph = (n, nnz,
    max_deg)``, a walk from every node, lowered; and the
    ``walk_transition`` events its trace recorded."""
    g = _ragged_shapes(sharding, graph)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    nodes = jax.ShapeDtypeStruct((graph[0],), jnp.int32, sharding=sharding)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prefix = span_event(WALK_TRANSITION_SCOPE, "")
    events = []

    def on_event(event, **_):
        if event.startswith(prefix):
            events.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        lowered = jax.jit(
            lambda g, key, nodes: _ragged_engine(g, graph[2]).step(key, nodes)
        ).lower(g, key, nodes)
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    return lowered, events


def test_auto_ragged_step_searches_kron16_rows_in_tiles(one_chip, monkeypatch):
    """At kron16's shapes the "auto" step's MH move is a two-level tiled
    search: no kernel, and at most 9 gathers in the whole step (the
    binary search took 21: 18 in the MH move, 3 in the hop loop)."""
    lowered, _ = _auto_ragged_step(one_chip, KRON16, monkeypatch)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r"\bgather\(", text)) <= 9


@pytest.mark.parametrize("graph,levels", [(KRON16, 2), (GRID25X40, 1)])
def test_auto_ragged_step_records_its_search_levels(
    one_chip, monkeypatch, graph, levels
):
    """Each trace of the "auto" ragged step records one backend event and
    one ``mh_search_levels_<L>`` event: L = 2 on kron16 (max degree
    9,810), L = 1 on the grid (max degree 5)."""
    _, events = _auto_ragged_step(one_chip, graph, monkeypatch)
    assert sorted(events) == [
        "/repro/walk_transition/backend_scan",
        f"/repro/walk_transition/mh_search_levels_{levels}",
    ]


def test_mamba2_370m_decode_step_fits_one_chip(one_chip):
    """One full-width mamba2-370m decode step (48 layers, d_model 1024,
    vocab 50280) at the serving batch, within one chip's HBM."""
    from repro.configs import get_arch
    from repro.models.factory import build_model

    cfg = get_arch("mamba2-370m")
    model = build_model(cfg, dtype=jnp.float32)
    batch, cache_len = 4, 256

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(lambda: model.init_cache(batch, cache_len)))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def step(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, tokens, cache, pos)
        return jnp.argmax(logits, axis=-1), cache

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens, pos
    ).compile()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < used < HBM_BYTES


def _sharded_fleet_scan_text(topo, **backend):
    """HLO of the mesh-sharded fleet scan on 4 described chips, walkers
    split over the mesh, the graph replicated."""
    from repro.walk_sgd import fleet as fleet_mod
    from repro.models import regression as reg

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    walkers = NamedSharding(mesh, PartitionSpec("data"))
    repl = NamedSharding(mesh, PartitionSpec())

    def s(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    g = jax.tree_util.tree_map(
        lambda v: s(v.shape, v.dtype), _ragged_shapes(None)
    )
    engine = _ragged_engine(g, walker_sharding=walkers, **backend)
    engine = dataclasses.replace(engine, p_j=s((), jnp.float32))
    fleet = fleet_mod.WalkFleet(
        engine=engine, nodes=s((W,), jnp.int32, walkers), num_walks=W,
        avg_every=4,
    )
    dim, steps = 10, 8
    return fleet_mod._fleet_scan.lower(
        s((2,), jnp.uint32),
        s((W, dim), jnp.float32, walkers),
        s((BA_N, dim), jnp.float32),
        s((BA_N,), jnp.float32),
        s((BA_N,), jnp.float32),
        fleet,
        steps,
        0.01,
        s((steps,), jnp.float32),
        True,
        reg.linear_grad,
    ).compile().as_text()


def test_sharded_fleet_scan_runs_one_kernel_per_shard(topo):
    """Asked for, the ragged kernel runs once per device on W/4 walkers,
    and the periodic average is an all-reduce."""
    text = _sharded_fleet_scan_text(topo, backend="pallas", interpret=False)
    kernel_calls = [
        line for line in text.splitlines()
        if "custom_call_target=\"tpu_custom_call\"" in line
    ]
    assert len(kernel_calls) == 1
    assert f"s32[{W // 4}]" in kernel_calls[0]
    assert "all-reduce" in text


def test_auto_sharded_fleet_scan_compiles(topo, monkeypatch):
    """Under "auto" on a TPU the sharded fleet runs the XLA gathers on
    each device's W/4 walkers, no kernel, and still averages by
    all-reduce."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _sharded_fleet_scan_text(topo)
    assert "tpu_custom_call" not in text
    assert f"s32[{W // 4}]" in text
    assert "all-reduce" in text


@pytest.mark.parametrize("layout", ["ragged", "sparse", "dense", "bucketed"])
def test_resolved_backend_tpu_rule(monkeypatch, layout):
    """"auto" is XLA (scan) on every layout, TPU included; an explicit or
    pinned pallas runs compiled on TPU where Mosaic accepts the kernel and
    raises on a rejected layout unless interpret mode was asked for."""
    g = barabasi_albert(40, 2, seed=0, layout="csr")
    if layout == "bucketed":
        g = g.to_bucketed()
    eng = WalkEngine.from_graph(
        g, MHLJParams(0.1, 0.5, 3), lipschitz=np.ones(g.n, np.float32),
        layout=None if layout == "bucketed" else layout,
    )
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert eng.resolved_backend == "scan"  # off TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng.resolved_backend == "scan"
    assert not eng.resolved_interpret
    pinned = dataclasses.replace(eng, backend="pallas")
    if layout in TPU_PALLAS_LAYOUTS:
        assert pinned.resolved_backend == "pallas"
        monkeypatch.setenv("REPRO_BACKEND", "pallas")
        assert eng.resolved_backend == "pallas"
    else:
        with pytest.raises(ValueError, match=layout):
            pinned.resolved_backend
        assert dataclasses.replace(
            pinned, interpret=True
        ).resolved_backend == "pallas"
        monkeypatch.setenv("REPRO_BACKEND", "pallas")
        with pytest.raises(ValueError, match=layout):
            eng.resolved_backend
