"""The control, the reference one precision lower (bfloat16) in the
program's place, comes out not correct under the cell's limits, and so
does the reference with half of each batch left out."""
import pytest

from bench import control, harness
from bench.cell import load_cell
from bench.tests import tiny_cell


@pytest.mark.parametrize("family", ["ssm"])
def test_control_and_half_batch_fail_the_limits(tmp_path, family):
    name = tiny_cell.write(tmp_path, family)
    cell = load_cell(name, tmp_path / "bench")
    limits = harness.limits_for(cell)
    got = control.readings(cell, 2**31 + 29)
    assert "half_batch" in got            # the tiny cell's batch is 2
    for case in ("control_bf16", "half_batch"):
        assert any(got[case][k] > limits[k]
                   for k in ("grad_norm_gap", "change_gap")), (case, got)
