#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 chipbench/calibrate.py --workload fleet.kron16 --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault fleet_half]

For each seed, in one process: the cell's set-up and warm-up, a window
of as many calls as a run compares, and the run's comparison (the
program's readings, or with ``--fault`` those of the program with that
fault planted).  For each control seed, also the numbers of the bfloat16
reference put in the program's place.  One JSON line per reading; the
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(spec, seed, control):
    import jax
    import numpy as np

    from chipbench import harness

    op = harness._load_module("ops", spec["traffic"]["op"]).build(spec, seed)
    op.warmup()
    # a fleet call's last step is judged against the next call's first node
    window_calls = op.compared_calls + (spec["traffic"]["op"] == "fleet")
    calls = []
    for _ in range(window_calls):
        i = op.calls_made
        jax.block_until_ready(op.launch(op.next_input(i)))
        calls.append(i)
    failed = op.failed_calls(calls)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    out = [("program", dict(op.compare(calls, rng), failed_calls=failed))]
    if control:
        out.append(("control", op.control()))
    return out


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", default="", help="seeds that also read the control")
    ap.add_argument("--fault", default=None, choices=sorted(
        {f for fs in faults.CELL_FAULTS.values() for f in fs}))
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.check_chips(spec["chips"])
    harness.enable_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if args.fault:
            with faults.planted(args.fault):
                got = readings(spec, seed, False)
            got = [(f"fault:{args.fault}", got[0][1])]
        else:
            got = readings(spec, seed, seed in controls)
        for kind, numbers in got:
            print(json.dumps({"workload": args.workload, "seed": seed, "kind": kind,
                              "numbers": numbers, "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
