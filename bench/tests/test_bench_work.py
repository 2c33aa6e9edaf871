"""Operation and byte counts of `bench/work` against hand counts made from
the published widths."""
import json
from pathlib import Path

import pytest

from bench.harness import load_module

BENCH = Path(__file__).parents[1]


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)["model"]


def _work(name):
    return load_module(BENCH / "work" / "configs" / f"{name}.py")


def test_xlstm_125m_flops_per_round():
    d, V, dm, H, N, K, fs, hd = 768, 50304, 1536, 4, 384, 4, 1024, 192
    mlstm = (d * dm * 2          # w_up, w_z
             + K * dm            # depthwise conv
             + 3 * dm * H * N    # q, k, v
             + 2 * dm * H        # input and forget gates
             + dm * d)           # w_down
    slstm = d * H * hd * 4 + H * hd * hd * 4 + d * 2 * fs + fs * d
    matrix = 7 * mlstm + slstm + d * V         # + unembedding
    assert matrix == 118_388_736
    mixer = 7 * 3 * H * (5 * N * N + 5 * N)    # mLSTM recurrence, fwd+bwd
    per_round = 4 * 1024 * (6 * matrix + mixer)
    assert per_round == 3_163_853_684_736
    assert _work("xlstm-125m").flops_per_round(
        _config("xlstm-125m"), 1024, 4) == per_round
    # a query24 round: one 128-token record
    assert _work("xlstm-125m").flops_per_round(
        _config("xlstm-125m"), 128, 1) == 128 * (6 * matrix + mixer)


@pytest.mark.parametrize("name,n_params", [("xlstm-125m", 157_037_596)])
def test_parameter_count_matches_the_program(name, n_params):
    import jax
    import jax.numpy as jnp

    from bench.cell import model_config
    from repro.models import build_model
    model = build_model(model_config(_config(name)))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes)) \
        == n_params


def test_stage_bytes():
    dp = load_module(BENCH / "work" / "stages" / "dp_round.py")
    # theta_L f32 in and out, gradient sum f32 in, bf16 row in and out
    assert dp.least_bytes(157_037_596, 2) == 16 * 157_037_596
    assert dp.least_bytes(1000, 4) == (4 + 4 + 4 + 4 + 4) * 1000
    clip = load_module(BENCH / "work" / "stages" / "clip_norm.py")
    assert clip.least_bytes(157_037_596) == 4 * 157_037_596
