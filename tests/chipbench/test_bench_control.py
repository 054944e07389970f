"""The control (the reference at float8 matmuls in the program's place)
and the half-batch fault read outside a cell's limits, at a tiny width."""
import pytest

from chipbench import cell as C
from chipbench import control, reference
from tiny import tiny_cell

CELLS = [w["name"] for w in C.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_and_half_batch_fail_the_limits(name):
    cell = tiny_cell(name)
    readings = control.stand_in_readings(cell, 2_147_483_661)
    for stand_in in ("control", "half_batch"):
        ok, checks = reference.judge(readings[stand_in], cell.limits)
        assert not ok, (stand_in, checks)
