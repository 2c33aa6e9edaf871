"""Resolve a benchmark cell by name: its configuration, traffic mix and
the numbers derived from them.

Everything a cell needs is data. `BENCHMARK.json` (beside this directory)
names the cell's configuration and traffic; the configuration lives in
`configs/<config>.json` and the traffic in `traffic/<traffic>.json`.
A new cell is a new entry and, where needed, new files: nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]      # configs/<config>.json
    traffic: Dict[str, Any]     # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path

    # ---- traffic, as the federation sees it ---------------------------
    @property
    def n_owners(self) -> int:
        return int(self.traffic["owners"])

    def epsilon(self, i: int) -> float:
        e = self.traffic["epsilon"]
        return float(e["base"] + (i % e["mod"]) * e.get("step", 1))

    def records(self, i: int) -> int:
        r = self.traffic["records"]
        return int(r["base"] + r["step"] * i)

    @property
    def seq(self) -> int:
        return int(self.traffic["seq"])

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def rounds(self) -> int:
        """Rounds per dispatch (K)."""
        return int(self.traffic["rounds_per_dispatch"])

    @property
    def horizon(self) -> int:
        return int(self.traffic["horizon"])

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    def metric_names(self, trace: bool) -> List[str]:
        """The metrics this cell reports: end-to-end without the trace,
        per-layer with it (each metric's `workloads` list, if any,
        decides whether the cell has it)."""
        group = self.per_layer if trace else self.end_to_end
        return [m["name"] for m in group
                if self.name in m.get("workloads", [self.name])]


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_dir: Optional[Path] = None) -> Cell:
    """The cell named `workload` in the BENCHMARK.json beside
    `bench_dir`; raises KeyError for a name it does not hold."""
    bench_dir = Path(bench_dir or BENCH_DIR)
    spec = _read(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; the benchmark has "
                       f"{sorted(cells)}")
    w = cells[workload]
    return Cell(name=w["name"], chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=_read(bench_dir / "configs" / f"{w['config']}.json"),
                traffic=_read(bench_dir / "traffic" / f"{w['traffic']}.json"),
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"],
                bench_dir=bench_dir)


def model_config(model: Dict[str, Any]):
    """The program's ModelConfig for a configuration file's "model"."""
    from repro.configs.base import ModelConfig, SSMConfig, XLSTMConfig
    m = dict(model)
    if m.get("xlstm") is not None:
        x = dict(m["xlstm"])
        x["slstm_indices"] = tuple(x.get("slstm_indices", ()))
        m["xlstm"] = XLSTMConfig(**x)
    if m.get("ssm") is not None:
        m["ssm"] = SSMConfig(**m["ssm"])
    return ModelConfig(**m)
