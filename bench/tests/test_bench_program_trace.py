"""The program's own spans and scopes, read by the per-layer metrics.

`fixtures/trace_scoped.json` holds a trace in the chip's form over a
10,000 ns window of two dispatches (2 rounds traced): device operations
labelled by their HLO text, the op_names of their program's instructions
(as the profile's HLO gives them), and the program's host spans.

  device 0, in program jit_run(42): fed.grad: fusion.9 -50..50 (50 ns in
            the window), the sLSTM backward 1000..4000, an mLSTM fusion
            400 ns, the pack 100 ns; fed.clip: the squared-norm kernel
            40 ns and a fusion whose operand is named `%fed.grad.7` 20
            ns; fed.noise: the dp_round kernel 100 ns; fed.bank_read 30
            ns; fed.bank_write 60 ns; fed.ledger 5 ns; an unscoped
            copy-start 100 ns; a while loop over the window (its time is
            its body's), an asynchronous copy's span of 3500 ns (it
            overlaps the work) and a fed.grad fusion after the window;
  device 1, in a program the profile holds no HLO of: one fusion of
            1000 ns whose label carries its fed.grad path.
Program spans: two run_rounds in the window (450 and 400 ns), each
holding two host_syncs and a launch; a run_rounds and two host_syncs
after it.
"""
import json
import os
import shutil
from pathlib import Path

import pytest

from bench import program_trace, traces
from bench.harness import Context, Spans, load_module

FIXTURES = Path(__file__).parent / "fixtures"
METRICS = Path(__file__).parents[1] / "metrics"
NEW = ("grad_ms_per_round", "slstm_ms_per_round", "privatize_ms_per_round",
       "bank_ms_per_round", "unscoped_share", "run_rounds_host_ms",
       "host_syncs_per_dispatch")


class FakeCell:
    bench_dir = Path(__file__).parents[1]


def _ctx(trace, program=None, rounds=2, cell=None):
    return Context(cell=cell or FakeCell(), trace=trace, rounds=rounds,
                   program=program)


def _read(ctx):
    return {name: load_module(METRICS / f"{name}.py").read(ctx)
            for name in NEW}


@pytest.fixture
def scoped():
    d = json.loads((FIXTURES / "trace_scoped.json").read_text())
    return traces.Trace.from_json(d), program_trace.ProgramTrace.from_json(d)


def test_readers_on_the_scoped_fixture(scoped):
    trace, program = scoped
    read = _read(_ctx(trace, program))
    # fed.grad: (50 + 3000 + 400 + 100 on device 0, 1000 on device 1) / 2
    # devices / 2 rounds = 1137.5 ns
    assert read["grad_ms_per_round"] == pytest.approx(1137.5e-6)
    # model.slstm: 3000 / 2 / 2
    assert read["slstm_ms_per_round"] == pytest.approx(750e-6)
    # fed.clip 40 + 20 (the operand named %fed.grad.7 is not fed.grad's),
    # fed.noise 100: 160 / 2 / 2
    assert read["privatize_ms_per_round"] == pytest.approx(40e-6)
    # bank_read 30 + bank_write 60: 90 / 2 / 2
    assert read["bank_ms_per_round"] == pytest.approx(22.5e-6)
    # the copy-start alone is unscoped: 100 of 3905 + 1000 ns
    assert read["unscoped_share"] == pytest.approx(100 * 100 / 4905)
    # run_rounds 450 + 400 ns over 2 dispatches; the spans after the
    # window do not count
    assert read["run_rounds_host_ms"] == pytest.approx(425e-6)
    assert read["host_syncs_per_dispatch"] == pytest.approx(2.0)


def test_readers_report_nothing_for_a_program_without_them(scoped):
    trace, _ = scoped
    # the benchmark's first fixture: unscoped operations, no program span
    bare = traces.load(FIXTURES / "trace_small.json")
    read = _read(_ctx(bare, program_trace.ProgramTrace([])))
    assert read == {name: None for name in NEW}
    # scopes without host spans, and host spans without scopes
    read = _read(_ctx(trace, program_trace.ProgramTrace([])))
    assert read["run_rounds_host_ms"] is None
    assert read["host_syncs_per_dispatch"] is None
    assert read["grad_ms_per_round"] is not None


def _dispatch(fed, state, spans, batches, seq, key):
    import jax
    with spans("prep"):
        seq = jax.device_put(seq)
    with spans("dispatch"):
        state, ms = fed.run_rounds(state, batches, seq, key)
    with spans("read_metrics"):
        ms["refused"].block_until_ready()
    with spans("wait"):
        jax.block_until_ready(state)
    return state


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A real CPU profile of two dispatches of a toy federation, written
    where run.py writes its profiles: <repo>/.bench_profile/profile-*."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.federation import (DataOwner, Federation, FederationConfig,
                                  PrivatizerConfig)
    repo = tmp_path_factory.mktemp("repo")
    (repo / "bench").mkdir()
    out = repo / ".bench_profile" / "profile-test"
    out.mkdir(parents=True)
    K, N = 4, 3
    owners = [DataOwner(n=100, epsilon=1.0, xi=1.0) for _ in range(N)]
    fed = Federation(owners, FederationConfig(horizon=50))
    fed.make_step(lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2),
                  privatizer=PrivatizerConfig(xi=1.0, granularity="example"))
    state = fed.init_state({"w": jnp.zeros((5,))})
    batches = {"x": jnp.ones((K, 2, 5)), "y": jnp.ones((K, 2))}
    seq = np.arange(K, dtype=np.int32) % N
    spans = Spans()
    state = _dispatch(fed, state, spans, batches, seq, jax.random.PRNGKey(0))
    spans.tracing = True
    jax.profiler.start_trace(str(out))
    for i in range(2):
        state = _dispatch(fed, state, spans, batches, seq,
                          jax.random.PRNGKey(i + 1))
    jax.profiler.stop_trace()
    return repo, out


def test_program_spans_from_a_real_profile(cpu_profile):
    repo, out = cpu_profile

    class Cell:
        bench_dir = repo / "bench"

    trace = traces.load_xplane(str(out))
    assert program_trace.profile_file(repo).startswith(str(out))
    ctx = _ctx(trace, cell=Cell())
    read = _read(ctx)
    # a caller-given device sequence: its bounds check reads min and max
    assert read["host_syncs_per_dispatch"] == 2.0
    assert read["run_rounds_host_ms"] > 0
    calls = program_trace.in_window(ctx.program.spans, trace.window,
                                    "run_rounds")
    dispatch = [s for s in trace.spans if s[0] == "dispatch"]
    assert len(calls) == len(dispatch) == 2
    # one clock: each run_rounds span lies inside the benchmark's dispatch
    for (_, a, b), (_, c, d) in zip(calls, dispatch):
        assert c <= a and b <= d


def test_another_runs_profile_is_refused(cpu_profile):
    repo, out = cpu_profile

    class Cell:
        bench_dir = repo / "bench"

    trace = traces.load_xplane(str(out))
    lo, hi = trace.window
    moved = traces.Trace(trace.devices, trace.spans, (lo, hi + 1))
    with pytest.raises(ValueError, match="another run's profile"):
        _read(_ctx(moved, cell=Cell()))


def test_op_names_from_a_real_profiles_hlo(cpu_profile):
    # the profile keeps each program's HLO; the fused round's instructions
    # carry the round's scopes in their op_name

    class Every:
        def __contains__(self, _):
            return True

    repo, out = cpu_profile
    names = program_trace.hlo_op_names(program_trace.profile_file(repo),
                                       Every())
    found = {s for ops in names.values() for path in ops.values()
             for s in program_trace._SCOPE.findall("/" + path)}
    # under vmap a scope reads `vmap(fed.grad)`
    assert set(program_trace.ROUND_SCOPES) <= found


def test_an_op_outside_its_programs_runs_is_matched_on_its_label(scoped):
    _, program = scoped
    op = traces.Op("fusion.2", "fusion.2 jit(run)/fed.noise/add", 10400, 5)
    assert program.op_path("/device:TPU:0", op) == op.label
    inside = traces.Op("fusion.2", "%fusion.2 = f32[4,4] fusion()", 4000, 5)
    assert "/fed.grad/model.mlstm/" in program.op_path("/device:TPU:0",
                                                      inside)


def test_the_newest_profile_is_read(cpu_profile, tmp_path):
    repo, out = cpu_profile
    path = program_trace.profile_file(repo)
    old = repo / ".bench_profile" / "profile-old" / "old.xplane.pb"
    old.parent.mkdir()
    shutil.copy(path, old)
    t = Path(path).stat().st_mtime - 60
    os.utime(old, (t, t))
    assert program_trace.profile_file(repo) == path
    with pytest.raises(FileNotFoundError):
        program_trace.profile_file(tmp_path)
