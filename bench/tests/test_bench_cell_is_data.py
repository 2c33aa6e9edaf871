"""A cell is data: a new configuration, traffic mix and cell, written
into a copy of the benchmark, run through the harness by name, with no
file that was there edited."""
import hashlib

import pytest

from bench.tests import tiny_cell


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


@pytest.mark.parametrize("family", ["ssm"])
def test_new_cell_runs_by_name_and_is_correct(tmp_path, monkeypatch,
                                              family):
    tiny_cell.interpret_kernels(monkeypatch)
    before = _digests(tiny_cell.BENCH.parent)
    name = tiny_cell.write(tmp_path, family)
    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())
    # config and its reference, mix, limits, work
    assert len(after) == len(before) + 5

    result = tiny_cell.run(tmp_path, name)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"rounds_per_s", "peak_hbm_gb",
                                      "setup_s"}
    assert list(result["checks"]) == ["grad_norm_gap", "change_gap",
                                      "ledger_gap", "window_compiles"]
