"""End-to-end decentralized training driver (deliverable b's e2e path).

Runs walk-orchestrated LLM training: a graph of data silos, MHLJ (or any
baseline) routing, per-silo token shards, a pjit-able train step, periodic
checkpointing, and metric logging.

CPU-scale invocation (examples/llm_decentralized.py uses this):

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b --scale smoke \
      --steps 100 --method mhlj

``--scale smoke`` trains the arch's reduced() variant on a 1-device mesh;
``--scale custom`` takes explicit --layers/--d-model/... for the ~100M-class
driver run; on a real TPU pod slice ``--scale full`` uses the production
mesh + fsdp_tp profile (same code path; the dry-run proves it lowers).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import ARCHITECTURES, get_arch, reduced
from repro.core import graphs as g_mod
from repro.core import schedules as pj_schedules
from repro.core.transition import MHLJParams
from repro.data.lm_data import make_node_token_shards
from repro.data.pipeline import NodeDataPipeline
from repro.models.factory import build_model
from repro.utils import checkpoint as ckpt
from repro.walk_sgd.llm_trainer import (
    WalkContext,
    init_walk_state,
    make_train_step,
)

__all__ = ["run_training", "main"]

GRAPHS = {
    "ring": lambda n, seed: g_mod.ring(n),
    "grid": lambda n, seed: g_mod.grid2d(int(np.sqrt(n))),
    "watts_strogatz": lambda n, seed: g_mod.watts_strogatz(n, 4, 0.1, seed),
    "erdos_renyi": lambda n, seed: g_mod.erdos_renyi(n, 0.1, seed),
    "expander": lambda n, seed: g_mod.expander(n, 6, seed),
}


def run_training(
    cfg,
    *,
    graph_kind: str = "ring",
    n_silos: int = 16,
    method: str = "mhlj",
    steps: int = 100,
    batch_size: int = 4,
    seq_len: int = 128,
    lr: float = 3e-4,
    p_j: float = 0.1,
    p_d: float = 0.5,
    r: int = 3,
    anneal_pj: bool = False,
    online_lipschitz: bool = True,
    seed: int = 0,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    log_every: int = 10,
    dtype=jnp.float32,
) -> dict:
    """Train; returns {'losses': ..., 'walk': ..., 'steps_per_sec': ...}."""
    graph = GRAPHS[graph_kind](n_silos, seed)
    n_silos = graph.n
    data = make_node_token_shards(
        n_silos, cfg.vocab_size, shard_len=max(2048, (seq_len + 1) * 4), seed=seed
    )
    pipeline = NodeDataPipeline(data, batch_size, seq_len, seed=seed)

    model = build_model(cfg, dtype=dtype)
    params = model.init(jax.random.PRNGKey(seed))
    optimizer = optim.adamw(lr)
    opt_state = optimizer.init(params)

    # method -> walk configuration (p_j=0 degrades MHLJ to plain MH-IS;
    # uniform Lipschitz degrades MH-IS to MH-uniform)
    if method == "mhlj":
        params_w = MHLJParams(p_j, p_d, r)
        lips0 = np.ones(n_silos, np.float32)
    elif method == "importance":
        params_w = MHLJParams(0.0, p_d, r)
        lips0 = np.ones(n_silos, np.float32)
    elif method == "uniform":
        params_w = MHLJParams(0.0, p_d, r)
        lips0 = np.ones(n_silos, np.float32)
        online_lipschitz = False  # keep L_v == 1 -> MH-uniform
    else:
        raise ValueError(f"unknown method {method!r}")

    walk = WalkContext.from_graph(graph, params_w, online_lipschitz=online_lipschitz)
    walk_state = init_walk_state(n_silos, lips0, v0=0, seed=seed, online=online_lipschitz)
    if anneal_pj and method == "mhlj":
        pj_sched = pj_schedules.polynomial_decay(p_j, steps, t0=max(1, steps // 4))
    else:
        pj_sched = np.full(steps, params_w.p_j, np.float32)

    # deterministic resume: restore params/opt/walk AND the pipeline counter
    # so a restarted job continues the SAME walk trajectory and batch stream
    # (Algorithm 1 is sequential — resuming from the wrong node silently
    # changes the sampled distribution)
    start_step = 0
    if resume and checkpoint_dir and ckpt.latest_step(checkpoint_dir) is not None:
        walk_state["p_j"] = jnp.asarray(0.0, jnp.float32)  # fix treedef for load
        out = ckpt.load_checkpoint(checkpoint_dir, params, opt_state, walk_state)
        params, opt_state = out["params"], out["opt_state"]
        walk_state = jax.tree_util.tree_map(jnp.asarray, out["walk_state"])
        start_step = out["step"]
        pipeline._counter = out["extra"].get("pipeline_counter", seed + start_step)

    step_fn = jax.jit(make_train_step(model, optimizer, walk), donate_argnums=(0, 1))

    losses, nodes = [], []
    t0 = time.time()
    for t in range(start_step, steps):
        node = int(walk_state["node"])
        batch = {k: jnp.asarray(v) for k, v in pipeline.next_batch(node).items()}
        walk_state["p_j"] = jnp.asarray(pj_sched[t], jnp.float32)
        params, opt_state, walk_state, metrics = step_fn(
            params, opt_state, walk_state, batch
        )
        losses.append(float(metrics["loss"]))
        nodes.append(node)
        if log_every and (t % log_every == 0 or t == steps - 1):
            print(
                f"step {t:5d}  node {node:3d}  loss {losses[-1]:.4f}  "
                f"w {float(metrics['weight']):.3f}",
                flush=True,
            )
        if checkpoint_dir and checkpoint_every and (t + 1) % checkpoint_every == 0:
            ckpt.save_checkpoint(
                checkpoint_dir, t + 1, params, opt_state, walk_state,
                extra={
                    "arch": cfg.name,
                    "method": method,
                    "pipeline_counter": pipeline._counter,
                },
            )
    dt = time.time() - t0
    hops = int(walk_state["hops"])
    updates = int(walk_state["updates"])
    return {
        "losses": np.asarray(losses),
        "update_nodes": np.asarray(nodes),
        "transitions_per_update": hops / max(updates, 1),
        "steps_per_sec": steps / dt,
        "params": params,
        "opt_state": opt_state,
        "walk_state": walk_state,
        "final_lipschitz": np.asarray(walk_state["lipschitz"]),
    }


def _custom_cfg(args):
    base = get_arch(args.arch)
    return dataclasses.replace(
        reduced(base),
        name=f"{args.arch}-custom",
        num_layers=args.layers,
        d_model=args.d_model,
        num_heads=args.heads,
        num_kv_heads=min(args.heads, base.num_kv_heads) or args.heads,
        head_dim=args.d_model // args.heads,
        d_ff=args.d_ff or 4 * args.d_model,
        vocab_size=args.vocab,
        loss_chunks=1,
    )


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-32b", choices=sorted(ARCHITECTURES))
    ap.add_argument("--scale", default="smoke", choices=["smoke", "custom", "full"])
    ap.add_argument("--graph", default="ring", choices=sorted(GRAPHS))
    ap.add_argument("--silos", type=int, default=16)
    ap.add_argument("--method", default="mhlj", choices=["mhlj", "importance", "uniform"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--p-j", type=float, default=0.1)
    ap.add_argument("--anneal-pj", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --checkpoint-dir")
    # --scale custom model dims (the ~100M-class driver)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=8192)
    args = ap.parse_args()

    if args.scale == "smoke":
        cfg = reduced(get_arch(args.arch))
    elif args.scale == "custom":
        cfg = _custom_cfg(args)
    else:
        cfg = get_arch(args.arch)

    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"method={args.method} graph={args.graph}({args.silos})", flush=True)
    res = run_training(
        cfg,
        graph_kind=args.graph,
        n_silos=args.silos,
        method=args.method,
        steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        lr=args.lr,
        p_j=args.p_j,
        anneal_pj=args.anneal_pj,
        seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
    )
    summary = {
        "loss_first10": float(res["losses"][:10].mean()),
        "loss_last10": float(res["losses"][-10:].mean()),
        "transitions_per_update": res["transitions_per_update"],
        "steps_per_sec": res["steps_per_sec"],
    }
    print(json.dumps(summary, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
