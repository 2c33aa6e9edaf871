"""A new cell written into a temporary copy of the benchmark, at a size
the CPU runs in seconds: a configuration with its plain reference, a
traffic mix, a work count, a cell entry and its limits, and nothing else
changed."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).parents[1]
ROOT = BENCH.parent

MODELS = {
    "ssm": {"name": "xlstm-tiny", "family": "ssm", "n_layers": 2,
            "d_model": 32, "n_heads": 2, "n_kv_heads": 2, "d_ff": 0,
            "vocab": 64, "head_dim": 16,
            "xlstm": {"slstm_indices": [1], "mlstm_proj_factor": 2.0,
                      "slstm_proj_factor": 1.3333333333333333,
                      "conv_kernel": 4},
            "long_context_override": None},
}
# the real cell whose limits, plain reference and work count a tiny cell
# borrows
LIKE = {"ssm": ("xlstm125m-silo16", "xlstm-125m")}


def write(tmp: Path, family: str) -> str:
    """Copy the benchmark under `tmp` and add a tiny cell of `family`;
    returns the cell's name."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg, mix, name = f"tiny-{family}", f"tiny-{family}", f"tiny-{family}-cell"
    (tmp / "bench" / "configs" / f"{cfg}.json").write_text(json.dumps(
        {"name": cfg, "model": MODELS[family]}))
    traffic = json.loads((BENCH / "traffic" / "silo16.json").read_text())
    traffic.update(owners=4, seq=16, batch=2, rounds_per_dispatch=4)
    (tmp / "bench" / "traffic" / f"{mix}.json").write_text(
        json.dumps(traffic))
    like_cell, like_cfg = LIKE[family]
    shutil.copy(BENCH / "limits" / f"{like_cell}.json",
                tmp / "bench" / "limits" / f"{name}.json")
    shutil.copy(BENCH / "configs" / f"{like_cfg}.py",
                tmp / "bench" / "configs" / f"{cfg}.py")
    shutil.copy(BENCH / "work" / "configs" / f"{like_cfg}.py",
                tmp / "bench" / "work" / "configs" / f"{cfg}.py")
    spec["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                              "chips": 1, "why": "a test cell"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


def run(tmp: Path, name: str, seed: int = 2**31 + 17):
    """Drive a whole run of the cell on the CPU: the harness's look for a
    chip is skipped, and the kernels run in the Pallas interpreter so the
    noise is drawn over the same padded blocks as on the chip."""
    import time

    import jax

    from bench import harness
    from bench.cell import load_cell
    t0 = time.perf_counter()
    cell = load_cell(name, tmp / "bench")
    return harness.run(cell, seed, 0.5, False, t0, jax.devices())


def interpret_kernels(monkeypatch):
    import repro.federation.deep as deep
    monkeypatch.setattr(deep, "resolve_interpret", lambda flag: True)
