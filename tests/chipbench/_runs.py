"""Helpers of the benchmark's tests: one run of a cell on the CPU."""
import io
import json
import time

from chipbench import faults, harness


def run_result(spec, seed, fault=None):
    """The result line of one run of ``spec`` with ``fault`` planted,
    skipping the look for a chip."""
    out, err = io.StringIO(), io.StringIO()
    t0 = time.perf_counter()
    if fault is None:
        harness.run_cell(spec, seed, 0.3, False, t_start=t0, require_chip=False,
                         out=out, err=err)
    else:
        with faults.planted(fault):
            harness.run_cell(spec, seed, 0.3, False, t_start=t0, require_chip=False,
                             out=out, err=err)
    return json.loads(out.getvalue().strip().splitlines()[-1])
