"""The lower-precision control fails the limits that the program passes:
the bfloat16 reference put in the program's place, read by the same
routine as ``chipbench/calibrate.py`` uses on the chip."""
import pytest

from chipbench import calibrate


@pytest.mark.parametrize("cell", ["walk.kron16", "walk.grid25x40", "fleet.kron16"])
def test_control_fails_where_the_program_passes(cell, tiny_spec):
    spec = tiny_spec(cell)
    limits = spec["limits"]
    (_, program), (_, control) = calibrate.readings(spec, 3000000013, True)
    assert program.pop("failed_calls") == 0
    assert all(program[k] <= limits[k] for k in program), program
    assert any(control[k] > limits[k] for k in control), control
