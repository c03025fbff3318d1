"""The control of ``correct``: the phase-2 table program computed in
float32, the precision below the float64 the configurations state,
differs from the plain reference where the float64 program does not
(``bench/control.py``; on the chip at the cells' own sizes, here at the
least trace length at which each cell's fleet probes)."""
import pytest

from bench import harness
from bench.control import readings
from bench.tests.sizes import cell_sizes

WORKLOADS = [w["name"] for w in harness.load_manifest()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_float32_control_is_not_correct(workload):
    (line,) = readings(workload, [1], 1, require_tpu=False,
                       requests=cell_sizes(workload)["control"],
                       ref_workers=0)
    assert line["program_rows_off"] == 0
    assert line["control_rows_off"] > 0
