"""The float32 control fails the comparison that the program passes, in
every cell, at a small size (PERF.md gives the readings at full size)."""
import pytest

from chipbench import compare, control, harness
from chipbench.tests.small import small_files

CELLS = [c["name"] for c in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_program_passes_and_control_fails(workload):
    _, (_, config, traffic) = small_files(workload)
    for row in control.readings(config, traffic, [4000000001, 77],
                                log=lambda s: None):
        assert all(row["program"][k] <= compare.LIMITS[k]
                   for k in row["program"]), row
        assert any(row["control"][k] > compare.LIMITS[k]
                   for k in row["control"]), row
