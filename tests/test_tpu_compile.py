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
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.engine import TPU_PALLAS_LAYOUTS, WalkEngine
from repro.core.graphs import barabasi_albert
from repro.core.transition import MHLJParams

# barabasi_albert(100_000, 3, seed=0, layout="ragged"): the repo's
# full-scale graph (benchmarks/large_graph_walk.py, serve_throughput.py)
BA_N, BA_NNZ, BA_MAX_DEG = 100_000, 699_832, 1196
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


def _ragged_shapes(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return dict(
        indptr=s((BA_N + 1,), jnp.int32),
        indices=s((BA_NNZ,), jnp.int32),
        edge_cdf=s((BA_NNZ,), jnp.float32),
        degrees=s((BA_N,), jnp.int32),
    )


def _ragged_engine(indptr, indices, edge_cdf, degrees, **kw):
    return WalkEngine(
        neighbors=None, degrees=degrees, p_j=0.1, p_d=0.5, r=R,
        layout="ragged", indptr=indptr, indices=indices, edge_cdf=edge_cdf,
        max_degree=BA_MAX_DEG, cdf_width=BA_MAX_DEG, **kw,
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

    def step(indptr, indices, edge_cdf, degrees, key, nodes):
        eng = _ragged_engine(
            indptr, indices, edge_cdf, degrees,
            backend="pallas", interpret=False,
        )
        return eng.step(key, nodes)

    compiled = jax.jit(step).lower(
        g["indptr"], g["indices"], g["edge_cdf"], g["degrees"], key, nodes
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


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


def test_sharded_fleet_scan_runs_one_kernel_per_shard(topo):
    """The mesh-sharded fleet scan on 4 described chips: the ragged kernel
    runs once per device on W/4 walkers, and the periodic average is an
    all-reduce."""
    from repro.walk_sgd import fleet as fleet_mod
    from repro.models import regression as reg

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    walkers = NamedSharding(mesh, PartitionSpec("data"))
    repl = NamedSharding(mesh, PartitionSpec())

    def s(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    g = {k: s(v.shape, v.dtype) for k, v in _ragged_shapes(None).items()}
    engine = _ragged_engine(
        g["indptr"], g["indices"], g["edge_cdf"], g["degrees"],
        backend="pallas", interpret=False, walker_sharding=walkers,
    )
    engine = dataclasses.replace(engine, p_j=s((), jnp.float32))
    fleet = fleet_mod.WalkFleet(
        engine=engine, nodes=s((W,), jnp.int32, walkers), num_walks=W,
        avg_every=4,
    )
    dim, steps = 10, 8
    compiled = fleet_mod._fleet_scan.lower(
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
    ).compile()
    text = compiled.as_text()
    kernel_calls = [
        line for line in text.splitlines()
        if "custom_call_target=\"tpu_custom_call\"" in line
    ]
    assert len(kernel_calls) == 1
    assert f"s32[{W // 4}]" in kernel_calls[0]
    assert "all-reduce" in text


@pytest.mark.parametrize("layout", ["ragged", "sparse", "dense", "bucketed"])
def test_resolved_backend_tpu_rule(monkeypatch, layout):
    """"auto" is the compiled kernel on TPU only where Mosaic accepts it,
    XLA (scan) elsewhere; an explicit pallas on a rejected layout raises
    on TPU unless interpret mode was asked for."""
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
    compiled = layout in TPU_PALLAS_LAYOUTS
    assert eng.resolved_backend == ("pallas" if compiled else "scan")
    assert not eng.resolved_interpret
    pinned = dataclasses.replace(eng, backend="pallas")
    if compiled:
        assert pinned.resolved_backend == "pallas"
    else:
        with pytest.raises(ValueError, match=layout):
            pinned.resolved_backend
        assert dataclasses.replace(
            pinned, interpret=True
        ).resolved_backend == "pallas"
        monkeypatch.setenv("REPRO_BACKEND", "pallas")
        with pytest.raises(ValueError, match=layout):
            eng.resolved_backend
