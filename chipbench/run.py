#!/usr/bin/env python3
"""Run one benchmark cell on the chip it is started on.

    python3 chipbench/run.py --workload walk.kron16 --seed 7 --seconds 30 --trace 0

Prints JSON lines: set-up phases, the window, the trace reduction (with
``--trace 1``), and as the last line the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``) and, last, ``checks``: each number compared beside
its limit.  Exits non-zero with no result where JAX finds no TPU, fewer
chips than the cell needs, or no program beside the benchmark.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import repro  # noqa: F401  the system under test must be beside the benchmark

    from chipbench import harness

    return harness.main(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
