"""Benchmark harness (deliverable d): one module per paper figure/claim plus
the system benchmarks.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--smoke] [--only fig3_ring,...]

Each module exposes ``run(quick) -> dict`` (with a ``derived`` summary) and
``PAPER_CLAIM``; results land in results/bench_<name>.json and a CSV line
``name,us_per_call,derived...`` is printed per benchmark (us_per_call =
wall time of the benchmark body).

``--smoke`` is the anti-rot tier exercised by the tier-1 test suite
(tests/test_bench_smoke.py): it verifies every module's harness contract
(NAME / PAPER_CLAIM / run) and *executes* the modules that define a
``run_smoke()`` tier at toy sizes — so a benchmark that stops importing or
crashes on its first step fails CI instead of rotting silently.  The
large-graph smoke tier additionally takes real walk steps through every
registered engine layout (``repro.core.engine.LAYOUTS`` — sparse, dense,
bucketed), so a layout cannot rot while the default one keeps passing.
Smoke results are not dumped to results/.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks import (
    fault_sweep,
    fig3_ring,
    fig4_erdos_renyi,
    fig5_sparse_graphs,
    fig6_annealing,
    large_graph_walk,
    law_sweep,
    llm_walk_throughput,
    multi_walk,
    serve_throughput,
    theorem1_remark1,
)
from benchmarks.common import dump, row, time_call

MODULES = [
    fig3_ring,
    fig4_erdos_renyi,
    fig5_sparse_graphs,
    fig6_annealing,
    theorem1_remark1,
    multi_walk,
    llm_walk_throughput,
    large_graph_walk,
    law_sweep,
    serve_throughput,
    fault_sweep,
]


def smoke(json_path: str | None = None) -> int:
    """Contract-check every module; execute the ones with a smoke tier.

    ``json_path`` additionally dumps ``{module: derived}`` for the executed
    smoke tiers — the input of ``benchmarks/check_regression.py``, which
    compares these steps/sec against the committed baseline.
    """
    failures = 0
    derived_by_module: dict = {}
    print("name,us_per_call,derived")
    for mod in MODULES:
        if not (
            isinstance(getattr(mod, "NAME", None), str)
            and isinstance(getattr(mod, "PAPER_CLAIM", None), str)
            and callable(getattr(mod, "run", None))
        ):
            failures += 1
            print(f"{getattr(mod, '__name__', mod)},0,FAILED: harness contract")
            continue
        if not callable(getattr(mod, "run_smoke", None)):
            print(f"{mod.NAME},0,import-ok")
            continue
        try:
            result, seconds = time_call(mod.run_smoke)
            derived_by_module[mod.NAME] = result.get("derived", {})
            print(row(f"{mod.NAME}[smoke]", seconds, result.get("derived", {})))
        except Exception as e:
            failures += 1
            print(f"{mod.NAME},0,FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    if json_path:
        with open(json_path, "w") as f:
            json.dump(derived_by_module, f, indent=2, default=float)
    return 1 if failures else 0


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes/iters")
    ap.add_argument(
        "--smoke", action="store_true",
        help="anti-rot tier: contract-check all modules, run toy sizes",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="with --smoke: dump per-module derived metrics to PATH "
        "(consumed by benchmarks/check_regression.py)",
    )
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args()

    if args.smoke:
        return smoke(json_path=args.json)

    selected = MODULES
    if args.only:
        names = set(args.only.split(","))
        selected = [m for m in MODULES if m.NAME in names]
        if not selected:
            print(f"no benchmarks match {args.only!r}", file=sys.stderr)
            return 2

    print("name,us_per_call,derived")
    failures = 0
    for mod in selected:
        try:
            result, seconds = time_call(mod.run, args.quick)
            derived = result.get("derived", {})
            if "error" in result:
                print(f"{mod.NAME},0,SKIPPED: {result['error']}")
                continue
            dump(mod.NAME, result)
            print(row(mod.NAME, seconds, derived))
        except Exception as e:
            failures += 1
            print(f"{mod.NAME},0,FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
