"""What the private round says about itself in a profile: named scopes in
the compiled round, and host spans in `Federation.run_rounds`.

The scopes are compile-time metadata: every `fed.*` scope and both xLSTM
block scopes must reach the compiled HLO's `op_name`s, the model scopes
under `jax.grad` too. The host spans are TraceMe events of the profiler
itself, nested in the caller's own span on one clock.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federation import (DataOwner, Federation, FederationConfig,
                              PrivatizerConfig)

N, K, B, S = 4, 2, 2, 16
FED_SCOPES = ("fed.grad", "fed.clip", "fed.noise", "fed.bank_read",
              "fed.bank_write", "fed.ledger")
MODEL_SCOPES = ("model.slstm", "model.mlstm")
# the names the benchmark's kernel stages match (bench/work/stages/): a
# scope holding one would pull other operations into a kernel's roofline
KERNEL_NAMES = ("dp_round", "sqnorm")


@pytest.fixture(scope="module")
def tiny():
    """A two-block xLSTM (one mLSTM, one sLSTM) behind the path the chip
    benchmark drives: packed parameters, a bf16 bank, per-record
    clipping, the fused privatizer, donation."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config("xlstm-125m").reduced(), d_model=32,
                              n_heads=2, n_kv_heads=2, head_dim=16, vocab=64)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    owners = [DataOwner(n=1000 + i, epsilon=2.0, xi=1.0) for i in range(N)]
    fed = Federation(owners, FederationConfig.from_target_lr(
        0.05, n_owners=N, horizon=100, sigma=1e-3, theta_max=10.0))
    fed.make_step(lambda p, b: model.loss(p, b)[0], pack_params=True,
                  bank_dtype=jnp.bfloat16,
                  privatizer=PrivatizerConfig(
                      xi=1.0, granularity="microbatch", n_microbatches=B,
                      fused_kernel=True),
                  donate=True)
    tok = jax.random.randint(jax.random.PRNGKey(1), (K, B, S), 0, cfg.vocab)
    return fed, params, {"tokens": tok, "labels": tok}


@pytest.fixture(scope="module")
def op_names(tiny):
    fed, params, batches = tiny
    state = fed.init_state(params)
    seq = jnp.arange(K, dtype=jnp.int32) % N
    keys = jax.random.split(jax.random.PRNGKey(2), K)
    text = fed._fused_fn.lower(state, batches, seq, keys).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(op_names):
    """Every path component of the op names that names a scope of the
    round or of the model."""
    return {part for name in op_names for part in name.split("/")
            for part in re.findall(r"(?:fed|model)\.\w+", part)}


def test_every_scope_reaches_the_compiled_round(op_names):
    assert set(FED_SCOPES) | set(MODEL_SCOPES) <= _scopes(op_names)
    # the model's scopes name its backward pass too
    for scope in MODEL_SCOPES:
        assert any(f"transpose(jvp({scope}))" in n for n in op_names), scope
    assert any("fed.grad/transpose(jvp(model.slstm))" in n
               for n in op_names)


def test_round_scopes_do_not_overlap(op_names):
    # each operation of the round sits under at most one fed.* scope
    for name in op_names:
        assert len({s for s in FED_SCOPES if s in name}) <= 1, name


def test_no_scope_carries_a_kernel_name(op_names):
    for scope in _scopes(op_names):
        assert not any(k in scope for k in KERNEL_NAMES), scope


def _events(log_dir):
    """(name, start, end) of every host-plane event of the trace."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_run_rounds_writes_its_host_spans_inside_the_callers(tiny,
                                                             tmp_path):
    fed, params, batches = tiny
    state = fed.init_state(params)
    seq = np.arange(K, dtype=np.int32) % N
    key = jax.random.PRNGKey(3)
    state, _ = fed.run_rounds(state, batches, seq, key)    # compiles
    jax.block_until_ready(state)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            state, ms = fed.run_rounds(state, batches, seq, key)
            jax.block_until_ready(state)
    events = _events(tmp_path)
    (_, c0, c1), = [e for e in events if e[0] == "caller"]

    def inside(name):
        return [e for e in events if e[0] == name
                and c0 <= e[1] and e[2] <= c1]

    (_, r0, r1), = inside("run_rounds")
    # the bounds check of a caller-given sequence reads its min and max
    syncs = inside("host_sync")
    assert len(syncs) == 2
    launch = inside("launch")
    assert len(launch) == 1
    for _, a, b in syncs + launch:
        assert r0 <= a and b <= r1
    assert np.asarray(ms["owner"]).tolist() == seq.tolist()


def test_a_drawn_sequence_never_syncs(tiny, tmp_path):
    # a schedule-drawn sequence stays on the device: no host_sync span
    fed, params, batches = tiny
    state = fed.init_state(params)
    key = jax.random.PRNGKey(4)
    state, _ = fed.run_rounds(state, batches, None, key)
    jax.block_until_ready(state)
    with jax.profiler.trace(str(tmp_path)):
        state, _ = fed.run_rounds(state, batches, None, key)
        jax.block_until_ready(state)
    names = [e[0] for e in _events(tmp_path)]
    assert names.count("run_rounds") == 1 and names.count("launch") == 1
    assert "host_sync" not in names
