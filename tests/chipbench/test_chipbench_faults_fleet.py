"""A run of the fleet cell, with the chip check skipped and the timed
path broken underneath, reports ``correct`` false for each fault the
cell can have, and true without one."""
import pytest
from _runs import run_result

from chipbench import faults


@pytest.mark.parametrize("fault", (None,) + faults.CELL_FAULTS["fleet"])
def test_fleet_cell_catches_faults(fault, tiny_spec, no_persistent_cache):
    result = run_result(tiny_spec("fleet.kron16"), 3000000012, fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert set(result["metrics"]) == {"updates_per_s", "setup_s"}
