"""Every cell of BENCHMARK.json resolves by name to its files, and each
of its limits lies between the readings it was set from."""
import json

import pytest

from bench.cell import load_cell
from bench.harness import limits_for
from bench.tests.tiny_cell import BENCH, ROOT

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_and_limits_lie_between_readings(name):
    cell = load_cell(name, BENCH)
    for path in (BENCH / "configs" / f"{cell.config_name}.py",
                 BENCH / "work" / "configs" / f"{cell.config_name}.py"):
        assert path.is_file(), path
    assert cell.n_owners * cell.records(0) > 0 and cell.rounds > 0
    assert cell.config["model"]["name"] == cell.config_name
    readings = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    assert set(limits_for(cell)) == {"grad_norm_gap", "change_gap",
                                     "ledger_gap"}
    for k, v in readings["limits"].items():
        if "lower" in v:
            assert v["lower"] < v["limit"], k
        if "upper" in v:
            assert v["limit"] < v["upper"], k
