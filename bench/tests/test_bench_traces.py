"""Trace reduction on a small recorded trace, against numbers worked out
by hand.

The fixture holds two devices over a 1,000 ns window:
  device 0: fusion.1 100-400, the dp_round kernel 400-500, the sqnorm
            kernel 450-550, an all-reduce 600-650, fusion.1 900-1100
            (clipped to 1000);
  device 1: fusion.1 over the whole window.
Host spans: prep 0-100, dispatch 100-150, read_metrics 150-1000.
"""
from pathlib import Path

import pytest

from bench import traces
from bench.harness import Context, load_module

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"
METRICS = Path(__file__).parents[1] / "metrics"


@pytest.fixture
def trace():
    return traces.load(FIXTURE)


def test_busy_and_idle_share(trace):
    # device 0 busy: [100, 550] + [600, 650] + [900, 1000] = 600 ns;
    # device 1 busy 1000 ns; mean 800 ns of a 1000 ns window
    assert traces.busy_s(trace) == pytest.approx(800e-9)
    assert traces.idle_share(trace) == pytest.approx(0.2)


def test_kernel_device_time(trace):
    dp = traces.op_seconds(trace, ("dp_round",))
    assert dp["/device:TPU:0"] == pytest.approx(100e-9)
    assert dp["/device:TPU:1"] == 0
    sq = traces.op_seconds(trace, ("sqnorm",))
    assert sq["/device:TPU:0"] == pytest.approx(100e-9)
    coll = traces.op_seconds(trace, (r"\ball-reduce",))
    assert coll == pytest.approx({"/device:TPU:0": 50e-9,
                                  "/device:TPU:1": 0.0})


def test_breakdown(trace):
    # fusion.1: (300 + 100 + 1000) / 2 devices; the rest / 2
    top = traces.top_ops(trace)
    assert [t[0] for t in top] == ["fusion.1", "custom-call.3",
                                   "custom-call.2", "all-reduce.7"]
    assert [t[1] for t in top] == pytest.approx([700e-9, 50e-9, 50e-9,
                                                 25e-9])
    # device 0's gaps: 650-900 and 550-600 under read_metrics, 0-100
    # under prep
    gaps = traces.idle_gaps(trace)
    assert [g[0] for g in gaps] == ["read_metrics", "prep", "read_metrics"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 100e-9, 50e-9])


def _ctx(trace, **kw):
    class FakeCell:
        model, seq, batch = {}, 1, 2
        traffic = {"bank_dtype": "bfloat16"}
        bench_dir = Path(__file__).parents[1]
    base = dict(cell=FakeCell(), trace=trace, rounds=2, n_params=1000,
                chips=2,
                peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12})
    base.update(kw)
    return Context(**base)


def test_metric_readers(trace):
    ctx = _ctx(trace)
    read = {name: load_module(METRICS / f"{name}.py").read(ctx) for name in
            ("idle_share", "dp_round_roofline", "clip_norm_roofline",
             "host_prep_ms")}
    assert read["idle_share"] == pytest.approx(20.0)
    # 16 B x 1000 params x 2 rounds at 1e12 B/s = 32 ns over 100 ns,
    # on the one device that ran the kernel
    assert read["dp_round_roofline"] == pytest.approx(32.0)
    # 4 B x 1000 params x 2 rounds x batch 2 = 16 ns over 100 ns
    assert read["clip_norm_roofline"] == pytest.approx(16.0)
    assert read["host_prep_ms"] == pytest.approx(100e-6)


def test_readers_return_nothing_without_their_ops(trace):
    bare = traces.Trace({"/device:TPU:0": [traces.Op("fusion.9", "fusion.9",
                                                     0, 10)]},
                        trace.spans, trace.window)
    ctx = _ctx(bare)
    for name in ("dp_round_roofline", "clip_norm_roofline"):
        assert load_module(METRICS / f"{name}.py").read(ctx) is None
