"""A run of a walk cell, with the chip check skipped and the timed path
broken underneath, reports ``correct`` false for each fault the cell can
have, and true without one."""
import pytest
from _runs import run_result

from chipbench import faults


@pytest.mark.parametrize("fault", (None,) + faults.CELL_FAULTS["walk"])
@pytest.mark.parametrize("cell", ["walk.kron16", "walk.grid25x40"])
def test_walk_cell_catches_faults(cell, fault, tiny_spec, no_persistent_cache):
    result = run_result(tiny_spec(cell), 3000000011, fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"walk_steps_per_s", "setup_s"}
