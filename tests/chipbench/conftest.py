"""Shared set-up of the benchmark's tests: the checkout root on the path,
and small copies of each cell that a CPU test run can hold."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def shrink(spec: dict) -> dict:
    """The cell at a CPU test's size: a Kronecker graph of scale 8 or a
    12 x 16 grid, 8 steps a call, two calls compared.  Everything else
    (chain, data law, walks per node, limits) is the cell's own."""
    cfg = spec["config"]
    if cfg["family"] == "kronecker":
        cfg["scale"] = 8
    else:
        cfg["rows"], cfg["cols"] = 12, 16
    spec["traffic"].update(steps_per_call=8, compared_walker_steps=1)
    return spec


@pytest.fixture
def tiny_spec():
    from chipbench import harness

    return lambda cell: shrink(harness.load_spec(cell))


@pytest.fixture
def no_persistent_cache(monkeypatch):
    """Runs in tests keep JAX's persistent cache off and out of the checkout."""
    from chipbench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
