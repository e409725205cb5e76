#!/usr/bin/env python3
"""Smoke test on a TPU: the MHLJ walk engine, the fleet trainer and
walk-routed serving, each driven once through the calls a user makes.

    python chip_smoke.py                phases A, B and C on jax.devices()[0]
    python chip_smoke.py --four-chips   phase B only: a 4-chip walker mesh
                                        against one device

All data comes from ``--seed``.  The graph is the repo's full-scale one,
``barabasi_albert(100_000, 3, layout="ragged")`` (about 700k directed
edges, max degree about 1.2k).

* A, walk engine: W=8192 walkers, 64 steps, compiled Pallas kernel against
  the scan backend; nodes and hops must agree bit for bit, every hop
  count must lie in Remark 1's range [1, r], and ``backend="auto"`` must
  resolve to scan (the kernel runs only when asked for).
* B, fleet trainer: ``run_rw_sgd_multi("mhlj", ...)`` with W=8192,
  ``avg_every=4`` on the chip, and the same call on the host CPU with the
  scan backend.  The walk streams must agree bit for bit, ``avg_mse`` must
  be finite and fall, and the two loss curves must agree within
  ``B_MSE_RTOL`` (the chip multiplies f32 matrices in one bf16 pass by
  default, the CPU does not).
* C, serving: ``repro.launch.serve`` at ``--scale full`` (mamba2-370m at
  its published widths), 512 mhlj walkers routing requests on the same
  graph.  At least 8 requests must complete, every generated id must lie
  in the vocabulary, and every offered request must end exactly once:
  completed, shed, or still waiting.
* --four-chips: phase B's call on a 4-chip walker mesh against one device.
  The walk streams must agree bit for bit and ``x_final`` as closely as
  ``tests/test_fleet.py`` asks, the loss curves within ``B_MSE_RTOL``, and
  with ``backend="pallas"`` the kernel must run once per device on W/4
  walkers.

One JSON line per phase, then the compile cache, then as the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, when a phase raises or when a check fails, the script exits
non-zero without that line.  Its seconds are smoke timings, with
compilation included where they say so; they are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.engine import WalkEngine  # noqa: E402
from repro.core.graphs import barabasi_albert  # noqa: E402
from repro.core.transition import MHLJParams  # noqa: E402
from repro.data.synthetic import make_heterogeneous_regression  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import cache_entries, enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_walker_mesh  # noqa: E402
from repro.walk_sgd.fleet import WalkFleet, shard_fleet  # noqa: E402
from repro.walk_sgd.trainer import run_rw_sgd_multi  # noqa: E402

N, M = 100_000, 3
PARAMS = MHLJParams(p_j=0.1, p_d=0.5, r=3)
A_WALKERS, A_STEPS = 8192, 64
B_WALKERS, B_STEPS, B_AVG_EVERY, B_DIM = 8192, 200, 4, 10
B_MSE_RTOL = 5e-2  # loss curves of two programs on the chip (one-pass bf16 f32 matmul)
C_WALKERS, C_TICKS, C_DRAIN, C_MIN_COMPLETED = 512, 300, 200, 8


class CheckFailed(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def phase_a(graph, seed: int, *, walkers=A_WALKERS, steps=A_STEPS):
    """Compiled ragged kernel against the scan backend, bit for bit."""
    rng = np.random.default_rng(seed)
    lips = jnp.asarray(np.exp(rng.normal(0.0, 1.0, graph.n)), jnp.float32)
    pallas = WalkEngine.from_graph(
        graph, PARAMS, lipschitz=lips, layout="ragged", backend="pallas",
        interpret=False,
    )
    engines = {"pallas": pallas, "scan": dataclasses.replace(pallas, backend="scan")}
    v0s = jnp.asarray(rng.integers(0, graph.n, walkers), jnp.int32)
    key = jax.random.PRNGKey(seed)
    out, compile_s, run_s = {}, {}, {}
    for name, eng in engines.items():
        t0 = time.perf_counter()
        run = jax.jit(lambda e, k, v: e.run(k, v, steps)).lower(eng, key, v0s).compile()
        compile_s[name] = time.perf_counter() - t0
        t0 = time.perf_counter()
        nodes, hops = jax.block_until_ready(run(eng, key, v0s))
        run_s[name] = time.perf_counter() - t0
        out[name] = (np.asarray(nodes), np.asarray(hops))
    node_mm = int((out["pallas"][0] != out["scan"][0]).sum())
    hop_mm = int((out["pallas"][1] != out["scan"][1]).sum())
    hops = out["pallas"][1]
    auto = dataclasses.replace(pallas, backend="auto", interpret=None)
    emit(
        "A_walk_engine",
        n=graph.n, nnz=int(graph.num_edges), max_degree=int(graph.max_degree),
        walkers=walkers, steps=steps,
        backend=pallas.resolved_backend, interpret=pallas.resolved_interpret,
        auto_backend=auto.resolved_backend,
        compile_s=compile_s, run_s=run_s,
        node_mismatches=node_mm, hop_mismatches=hop_mm,
        hops_min=int(hops.min()), hops_max=int(hops.max()),
        jump_share=float((hops != 1).mean()),
    )
    check(node_mm == 0 and hop_mm == 0, "pallas and scan walk streams differ")
    check(hops.min() >= 1 and hops.max() <= PARAMS.r, "hops outside [1, r]")
    return auto.resolved_backend


def _fleet_setup(graph, seed: int):
    data = make_heterogeneous_regression(graph.n, dim=B_DIM, seed=seed)
    engine = WalkEngine.from_graph(
        graph, PARAMS, lipschitz=jnp.asarray(data.lipschitz, jnp.float32),
        layout="ragged",
    )
    return data, engine, 0.3 / float(data.lipschitz.mean())


def _train(graph, data, engine, gamma, seed, walkers, steps, mesh=None):
    t0 = time.perf_counter()
    res = run_rw_sgd_multi(
        "mhlj", graph, data, gamma, steps, walkers, mhlj_params=PARAMS,
        avg_every=B_AVG_EVERY, seed=seed, engine=engine, mesh=mesh,
    )
    return res, time.perf_counter() - t0


def _rel_gap(a, b) -> float:
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _stream_mismatches(a, b):
    return (
        int((a.update_nodes != b.update_nodes).sum()),
        int((a.transitions != b.transitions).sum()),
    )


def phase_b(graph, seed: int, *, walkers=B_WALKERS, steps=B_STEPS):
    """The fleet trainer on the chip, and the same run on the host CPU."""
    data, engine, gamma = _fleet_setup(graph, seed)
    chip, chip_s = _train(graph, data, engine, gamma, seed, walkers, steps)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        # "auto" reads jax.default_backend(), which still names the chip
        # here, so the host leg states its backend
        host_engine = jax.device_put(dataclasses.replace(engine, backend="scan"), cpu)
        host, host_s = _train(graph, data, host_engine, gamma, seed, walkers, steps)
    node_mm, hop_mm = _stream_mismatches(chip, host)
    gap = _rel_gap(chip.avg_mse, host.avg_mse)
    emit(
        "B_fleet_trainer",
        walkers=walkers, steps=steps, avg_every=B_AVG_EVERY, dim=B_DIM,
        backend=engine.resolved_backend, host_backend="scan",
        chip_s_with_compile=chip_s, host_s_with_compile=host_s,
        avg_mse_first=float(chip.avg_mse[0]), avg_mse_last=float(chip.avg_mse[-1]),
        node_mismatches=node_mm, hop_mismatches=hop_mm,
        avg_mse_max_rel_gap=gap, avg_mse_rtol=B_MSE_RTOL,
    )
    check(np.isfinite(chip.avg_mse).all(), "non-finite avg_mse on the chip")
    check(chip.avg_mse[-1] < chip.avg_mse[0], "avg_mse did not fall")
    check(node_mm == 0 and hop_mm == 0, "chip and host walk streams differ")
    check(gap <= B_MSE_RTOL, f"avg_mse gap {gap} above {B_MSE_RTOL}")


def phase_b_four_chips(graph, seed: int, *, walkers=B_WALKERS, steps=B_STEPS):
    """The mesh-sharded fleet on four chips against one device, by the
    contract of tests/test_fleet.py's multi-device case."""
    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, found {len(jax.devices())}")
    data, engine, gamma = _fleet_setup(graph, seed)
    mesh = make_walker_mesh(4)
    sharded, sharded_s = _train(graph, data, engine, gamma, seed, walkers, steps, mesh)
    single, single_s = _train(graph, data, engine, gamma, seed, walkers, steps)
    node_mm, hop_mm = _stream_mismatches(sharded, single)

    # asked for, the kernel must run once per device on its own W/4 walkers
    pinned = dataclasses.replace(engine, backend="pallas", interpret=False)
    fleet = shard_fleet(WalkFleet.create(pinned, walkers, seed=seed), mesh)
    text = (
        jax.jit(lambda f, k: f.engine.step(k, f.nodes))
        .lower(fleet, jax.random.PRNGKey(seed)).compile().as_text()
    )
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    per_device = f"s32[{walkers // 4}]"

    # The models themselves must agree as tightly as on the CPU; the loss
    # curves are 100k-row reductions that the two programs may multiply in
    # different passes on the chip, so they get phase B's tolerance.
    mse_gap = _rel_gap(sharded.mse, single.mse)
    avg_gap = _rel_gap(sharded.avg_mse, single.avg_mse)
    emit(
        "B_fleet_four_chips",
        walkers=walkers, steps=steps, avg_every=B_AVG_EVERY, devices=len(mesh.devices.flat),
        backend=engine.resolved_backend,
        sharded_s_with_compile=sharded_s, single_s_with_compile=single_s,
        node_mismatches=node_mm, hop_mismatches=hop_mm,
        mse_max_rel_gap=mse_gap, avg_mse_max_rel_gap=avg_gap, mse_rtol=B_MSE_RTOL,
        x_final_max_abs_diff=float(np.max(np.abs(sharded.x_final - single.x_final))),
        kernel_calls_per_device_program=len(calls),
        kernel_walkers_per_device=walkers // 4 if calls and per_device in calls[0] else None,
    )
    check(node_mm == 0 and hop_mm == 0, "sharded and single-device walk streams differ")
    check(mse_gap <= B_MSE_RTOL, f"mse gap {mse_gap} above {B_MSE_RTOL}")
    check(avg_gap <= B_MSE_RTOL, f"avg_mse gap {avg_gap} above {B_MSE_RTOL}")
    check(
        np.allclose(sharded.x_final, single.x_final, rtol=1e-4, atol=1e-6),
        "x_final differs beyond rtol 1e-4, atol 1e-6",
    )
    check(len(calls) == 1 and per_device in calls[0], "kernel not partitioned per device")


def phase_c(graph, seed: int, *, walkers=C_WALKERS, ticks=C_TICKS, drain=C_DRAIN):
    """Walk-routed serving as ``python -m repro.launch.serve`` builds it."""
    args = serve.build_parser().parse_args([
        "--scale", "full", "--nodes", str(graph.n), "--ba-m", str(M),
        "--walkers", str(walkers), "--method", "mhlj", "--ticks", str(ticks),
        "--drain", str(drain), "--seed", str(seed),
    ])
    t0 = time.perf_counter()
    engine = serve.build_engine(args)
    sim = serve.build_simulator(args, engine, graph=graph)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    metrics = sim.run(args.ticks, drain_ticks=args.drain)
    run_s = time.perf_counter() - t0

    cfg = engine.cfg
    tokens = [t for r in engine.completed for t in r.generated]
    ends = {
        "completed": [r.rid for r in engine.completed],
        "shed": [r.rid for r in engine.shed_requests],
        "waiting_at_node": [r.rid for dq in sim.pending.values() for r in dq],
        "queued": [r.rid for r in engine.queue],
        "in_slot": [r.rid for r in engine.slots if r is not None],
    }
    ended = [rid for rids in ends.values() for rid in rids]
    emit(
        "C_serving",
        arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model, vocab=cfg.vocab_size,
        walkers=walkers, ticks=ticks, drain=drain,
        route_backend=sim.route_engine.resolved_backend,
        route_interpret=sim.route_engine.resolved_interpret,
        offered=metrics["offered"], **{k: len(v) for k, v in ends.items()},
        generated_tokens=len(tokens),
        p50_ticks=metrics["p50_ticks"], p99_ticks=metrics["p99_ticks"],
        build_s=build_s, run_s_with_compile=run_s,
    )
    check(metrics["completed"] >= C_MIN_COMPLETED, f"fewer than {C_MIN_COMPLETED} completed")
    check(all(0 <= t < cfg.vocab_size for t in tokens), "generated id outside the vocabulary")
    check(
        sorted(ended) == list(range(metrics["offered"])),
        "an offered request did not end exactly once",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet trainer, sharded over 4 chips")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    t0 = time.perf_counter()
    graph = barabasi_albert(N, M, seed=args.seed, layout="ragged")
    emit("graph", n=graph.n, nnz=int(graph.num_edges), max_degree=int(graph.max_degree),
         build_s=time.perf_counter() - t0)
    if args.four_chips:
        phase_b_four_chips(graph, args.seed)
    else:
        auto = phase_a(graph, args.seed)
        check(auto == "scan", f"'auto' resolved to {auto!r} for the ragged layout")
        phase_b(graph, args.seed)
        phase_c(graph, args.seed)
    emit("compile_cache", dir=cache_dir, entries=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
