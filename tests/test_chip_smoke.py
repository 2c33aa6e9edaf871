"""chip_smoke.py off the chip: its main phase on a reduced model, its
refusal to run without a TPU, and where the compile cache goes."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_main_phase_runs_reduced_xlstm_through_the_oracle(chip_smoke):
    from repro.configs import get_config
    r = chip_smoke.train(get_config("xlstm-125m").reduced(), seq=16,
                         batch=4, records=16)
    n_rounds = chip_smoke.DISPATCHES * chip_smoke.ROUNDS
    assert r["granted"] + r["refused"] == n_rounds
    assert r["granted"] > 0 and r["refused"] > 0     # both outcomes ran
    assert sum(o["responses"] for o in r["ledger"].values()) == r["granted"]
    assert np.isfinite([r["loss_before"], r["loss_after"]]).all()
    assert len(r["dispatch_s"]) == chip_smoke.DISPATCHES
    # off TPU the kernel's jnp oracle stands in, so no Pallas call
    assert r["kernel_in_program"] is False
    assert r["compile_s"] > 0


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_dir_honours_the_environment():
    from repro.launch.compile_cache import ENV_VAR, compile_cache_dir
    assert compile_cache_dir({ENV_VAR: "/elsewhere"}) == "/elsewhere"
    assert compile_cache_dir({}) == str(ROOT / ".jax_cache")
    assert compile_cache_dir({ENV_VAR: ""}) == str(ROOT / ".jax_cache")


def test_enable_compile_cache_sets_nothing_when_the_variable_is_set(
        monkeypatch):
    import jax

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, "/from/the/environment")
    assert enable_compile_cache() == "/from/the/environment"
    assert jax.config.jax_compilation_cache_dir == before
