"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape) on the production meshes and capture
memory / cost / collective analyses for the roofline (deliverable g).

MUST set the fake-device flag before ANY jax import (jax locks the device
count at first init).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.configs import (  # noqa: E402
    ARCHITECTURES,
    INPUT_SHAPES,
    arch_for_shape,
    get_arch,
    get_shape,
)
from repro.core.graphs import ring  # noqa: E402
from repro.core.transition import MHLJParams  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.factory import build_model  # noqa: E402
from repro.sharding import rules as sh  # noqa: E402
from repro.utils.hlo_parse import collective_summary  # noqa: E402
from repro.walk_sgd.llm_trainer import (  # noqa: E402
    WalkContext,
    init_walk_state,
    make_serve_step,
    make_train_step,
)

N_SILOS = 64  # graph nodes (data silos) for the walk-orchestrated train step


def make_optimizer(cfg):
    if cfg.optimizer == "adafactor":
        return optim.adafactor(1e-3)
    return optim.adamw(3e-4)


def _decode_profile(cfg) -> str:
    # pure-TP decode needs params to fit one model-parallel group: use the
    # 2-D profile for very large archs (DESIGN.md §5)
    return "fsdp_decode" if cfg.param_count() * 2 > 120e9 else "tp_decode"


def lower_case(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    extra: dict | None = None,
    unroll: bool = False,
    model_parallel: int = 16,
):
    """Returns (lowered, compiled, info) for one (arch, shape, mesh) case.

    ``unroll=True`` fully unrolls layer scans so cost_analysis /
    collective_summary count EVERY layer (XLA prices a while body once) —
    the roofline capture mode.  Rolled scan remains the deployment default.
    """
    if unroll:
        from repro.models.model_utils import unrolled_layers

        with unrolled_layers():
            return _lower_case_inner(
                arch_name, shape_name, multi_pod, extra, True, model_parallel
            )
    return _lower_case_inner(
        arch_name, shape_name, multi_pod, extra, False, model_parallel
    )


def _lower_case_inner(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    extra: dict | None,
    unrolled: bool,
    model_parallel: int = 16,
):
    shape = get_shape(shape_name)
    cfg = arch_for_shape(get_arch(arch_name), shape)
    if extra:
        import dataclasses

        cfg = dataclasses.replace(cfg, **extra)
    mesh = make_production_mesh(multi_pod=multi_pod, model_parallel=model_parallel)
    model = build_model(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    if shape.kind == "train":
        profile = "fsdp_tp"
        optimizer = make_optimizer(cfg)
        opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
        graph = ring(N_SILOS)
        walk = WalkContext.from_graph(graph, MHLJParams(0.1, 0.5, 3))
        step = make_train_step(model, optimizer, walk)
        walk_shapes = jax.eval_shape(
            lambda: init_walk_state(N_SILOS, np.ones(N_SILOS, np.float32))
        )
        batch_shapes = model.input_specs(shape)

        p_spec = sh.param_specs(params_shapes, profile, mesh)
        o_spec = sh.opt_state_specs(opt_shapes, p_spec, params_shapes, profile, mesh)
        w_spec = jax.tree_util.tree_map(lambda _: jax.sharding.PartitionSpec(), walk_shapes)
        b_spec = sh.batch_specs(batch_shapes, profile, mesh)
        in_sh = tuple(
            sh.named_shardings(s, mesh) for s in (p_spec, o_spec, w_spec, b_spec)
        )
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        out_sh = (in_sh[0], in_sh[1], in_sh[2], rep)
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(0, 1))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_shapes, opt_shapes, walk_shapes, batch_shapes)
    elif shape.kind == "prefill":
        profile = _decode_profile(cfg)

        def prefill_step(params, batch):
            hidden = model.apply(params, batch)
            logits = jnp.einsum(
                "bd,vd->bv", hidden[:, -1], params["embedding"]["table"],
                preferred_element_type=jnp.float32,
            )
            return logits

        batch_shapes = model.input_specs(shape)
        p_spec = sh.param_specs(params_shapes, profile, mesh)
        b_spec = sh.batch_specs(batch_shapes, profile, mesh)
        in_sh = tuple(sh.named_shardings(s, mesh) for s in (p_spec, b_spec))
        fn = jax.jit(prefill_step, in_shardings=in_sh)
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_shapes, batch_shapes)
    else:  # decode
        profile = _decode_profile(cfg)
        serve = make_serve_step(model)
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        tok_shapes = model.input_specs(shape, for_decode=True)["tokens"]
        p_spec = sh.param_specs(params_shapes, profile, mesh)
        c_spec = sh.cache_specs(cache_shapes, profile, mesh)
        t_spec = sh.batch_specs({"t": tok_shapes}, profile, mesh)["t"]
        rep_spec = jax.sharding.PartitionSpec()
        in_sh = (
            sh.named_shardings(p_spec, mesh),
            sh.named_shardings(c_spec, mesh),
            jax.sharding.NamedSharding(mesh, t_spec),
            jax.sharding.NamedSharding(mesh, rep_spec),
        )
        out_sh = (in_sh[2], in_sh[1])
        fn = jax.jit(serve, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(1,))
        pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_shapes, cache_shapes, tok_shapes, pos_shape)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}
    coll = collective_summary(compiled.as_text())
    info = {
        "arch": arch_name,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "unrolled": unrolled,
        "model_parallel": model_parallel,
        "kind": shape.kind,
        "profile": profile,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "memory": mem_info,
        "collectives": coll,
        "compile_seconds": compile_s,
    }
    return lowered, compiled, info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument(
        "--unroll", action="store_true",
        help="fully unroll layer scans (roofline capture: per-layer costs counted)",
    )
    args = ap.parse_args()

    archs = list(ARCHITECTURES) if args.arch == "all" else args.arch.split(",")
    shapes = (
        [s.name for s in INPUT_SHAPES] if args.shape == "all" else args.shape.split(",")
    )
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    _, compiled, info = lower_case(arch, shape, mp, unroll=args.unroll)
                    info["status"] = "ok"
                    print(
                        f"[OK]   {tag}: flops={info['flops']:.3e} "
                        f"bytes={info['bytes_accessed']:.3e} "
                        f"coll={info['collectives']['total_bytes']:.3e}B "
                        f"compile={info['compile_seconds']:.1f}s",
                        flush=True,
                    )
                    del compiled
                except Exception as e:
                    info = {
                        "arch": arch, "shape": shape, "multi_pod": mp,
                        "status": "fail", "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                results.append(info)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(info) + "\n")
    n_ok = sum(r["status"] == "ok" for r in results)
    print(f"\n{n_ok}/{len(results)} cases lowered+compiled successfully", flush=True)
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
