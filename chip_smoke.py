"""Smoke run of the federation's main path on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh-sharded bank, four chips

One chip: xlstm-125m at its published widths (12 blocks, d_model 768,
vocab 50304; random weights from a seed) is trained privately by 8 data
owners through the session API, `Federation.make_step` -> `init_state`
-> `run_rounds` -> `reconcile`: 3 dispatches of 8 fused rounds on the
flat engine, with a bf16 owner bank and the fused `dp_round` Pallas
kernel. The script fails unless the compiled round holds the kernel
(`tpu_custom_call`), the loss stays finite, and the reconciled ledger
counts exactly the rounds the device granted.

`--chips 4` runs only the sharded engine: the same rounds with the bank's
owner rows over four chips (mesh data=4), then on a mesh of one chip.
The reconciled ledgers must be equal and theta_L must agree to the
tolerance of tests/test_sharded_engine.py.

The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "xlstm-125m"
N_OWNERS = 8
EPSILON = 2.0
SEQ, BATCH = 256, 8
ROUNDS, DISPATCHES = 8, 3
RECORDS = 2048               # records per owner shard
HORIZON = 4                  # per-owner response cap: 24 rounds over 8
                             # owners draw refusals as well as grants
THETA_RTOL, THETA_ATOL = 2e-5, 2e-6   # tests/test_sharded_engine.py


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def train(cfg, *, mesh=None, seq: int = SEQ, batch: int = BATCH,
          rounds: int = ROUNDS, dispatches: int = DISPATCHES,
          records: int = RECORDS, inspect: bool = True) -> dict:
    """Privately train `cfg` for `dispatches` x `rounds` fused rounds and
    check the run. Returns what it observed; raises on a failed check.

    `inspect` compiles the fused round ahead of the first dispatch, to
    time the compile and to look for the Pallas kernel in the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import OwnerDataPipeline, synthetic_owner_shards
    from repro.federation import (DataOwner, Federation, FederationConfig,
                                  PrivatizerConfig)
    from repro.models import build_model

    model = build_model(cfg, remat=False)
    params = jax.jit(lambda k: model.init(k, jnp.float32))(
        jax.random.PRNGKey(0))
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    pipe = OwnerDataPipeline(
        synthetic_owner_shards(N_OWNERS, records, seq, cfg.vocab, seed=0),
        batch, seed=0)
    eval_batch = {k: jnp.asarray(v) for k, v in synthetic_owner_shards(
        1, batch, seq, cfg.vocab, seed=1)[0].next_batch(batch).items()}

    fed = Federation(
        [DataOwner(n=n, epsilon=EPSILON, xi=1.0) for n in pipe.owner_sizes],
        FederationConfig.from_target_lr(0.05, n_owners=N_OWNERS,
                                        horizon=HORIZON, sigma=1e-2,
                                        theta_max=100.0))

    def loss_fn(p, b):
        return model.loss(p, b)[0]

    fed.make_step(loss_fn, pack_params=True, bank_dtype=jnp.bfloat16,
                  privatizer=PrivatizerConfig(xi=1.0,
                                              granularity="microbatch",
                                              n_microbatches=4,
                                              fused_kernel=True),
                  donate=True, mesh=mesh)
    state = fed.init_state(params)
    del params
    eval_loss = jax.jit(lambda theta, b: loss_fn(theta.unpack(), b))
    loss_before = float(eval_loss(state.theta_L, eval_batch))

    key = jax.random.PRNGKey(0)
    granted = np.zeros(N_OWNERS, np.int64)
    refused = np.zeros(N_OWNERS, np.int64)
    compile_s, kernel, dispatch_s = None, None, []
    for d in range(dispatches):
        owner_seq = jnp.asarray(pipe.schedule(rounds), jnp.int32)
        batches = {k: jnp.asarray(v)
                   for k, v in pipe.batches_for(owner_seq).items()}
        key, sub = jax.random.split(key)
        if inspect and d == 0:
            t = time.perf_counter()
            compiled = fed._fused_fn.lower(
                state, batches, owner_seq,
                jax.random.split(sub, rounds)).compile()
            compile_s = time.perf_counter() - t
            kernel = "tpu_custom_call" in compiled.as_text()
            del compiled
        t = time.perf_counter()
        state, ms = fed.run_rounds(state, batches, owner_seq, key=sub)
        jax.block_until_ready(state)
        dispatch_s.append(time.perf_counter() - t)
        was_refused = np.asarray(ms["refused"])
        owners = np.asarray(ms["owner"])
        np.add.at(granted, owners[~was_refused], 1)
        np.add.at(refused, owners[was_refused], 1)

    loss_after = float(eval_loss(state.theta_L, eval_batch))
    ledger = fed.reconcile(state)
    check(np.isfinite(loss_before) and np.isfinite(loss_after),
          f"finite loss ({loss_before} -> {loss_after})")
    check(bool(jnp.isfinite(state.theta_L.buf).all()), "finite theta_L")
    check([ledger[i]["responses"] for i in range(N_OWNERS)]
          == granted.tolist(),
          f"ledger responses {[ledger[i]['responses'] for i in ledger]} "
          f"== granted rounds {granted.tolist()}")
    check([ledger[i]["refused"] for i in range(N_OWNERS)]
          == refused.tolist(), "ledger refusals == refused rounds")
    bank_bytes = {}
    for leaf in jax.tree_util.tree_leaves(state.bank):
        for shard in leaf.addressable_shards:
            bank_bytes[shard.device.id] = (bank_bytes.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    return {"params": n_params, "granted": int(granted.sum()),
            "refused": int(refused.sum()), "loss_before": loss_before,
            "loss_after": loss_after, "compile_s": compile_s,
            "kernel_in_program": kernel, "dispatch_s": dispatch_s,
            "ledger": ledger, "theta": np.asarray(state.theta_L.buf),
            "bank_bytes_per_device": bank_bytes}


def _report(name: str, r: dict) -> None:
    print(f"[{name}] P: {r['params']}")
    print(f"[{name}] owners: {N_OWNERS} x epsilon {EPSILON}, "
          f"{DISPATCHES} dispatches x {ROUNDS} rounds")
    print(f"[{name}] granted: {r['granted']}  refused: {r['refused']}")
    print(f"[{name}] loss before: {r['loss_before']!r}  "
          f"after: {r['loss_after']!r}")
    if r["compile_s"] is not None:
        print(f"[{name}] fused-round compile seconds: {r['compile_s']!r}")
    print(f"[{name}] dispatch wall seconds (smoke timing, not a metric): "
          f"{r['dispatch_s']!r}")
    print(f"[{name}] bank bytes per device: {r['bank_bytes_per_device']}")


def _peak_bytes(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    return ", ".join(f"{d.id}: {s.get('peak_bytes_in_use', 'not reported')}"
                     for d, s in zip(devices, stats))


def one_chip(cfg) -> None:
    import jax
    r = train(cfg)
    _report("1 chip", r)
    print(f"[1 chip] tpu_custom_call in fused round: {r['kernel_in_program']}")
    check(r["kernel_in_program"], "the compiled fused round holds the "
          "Pallas kernel (tpu_custom_call)")
    print(f"peak bytes in use: {_peak_bytes(jax.devices())}")


def four_chips(cfg) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_debug_mesh, make_host_mesh
    mesh = make_host_mesh()
    print(f"mesh: {dict(mesh.shape)}")
    sharded = train(cfg, mesh=mesh, inspect=False)
    _report("4 chips", sharded)
    check(len(sharded["bank_bytes_per_device"]) == 4,
          "bank spread over 4 devices")
    single = train(cfg, mesh=make_debug_mesh(1, 1), inspect=False)
    _report("1-chip mesh", single)
    check(sharded["ledger"] == single["ledger"],
          "reconciled ledgers equal across meshes")
    print("reconciled ledgers equal: True")
    np.testing.assert_allclose(sharded["theta"], single["theta"],
                               rtol=THETA_RTOL, atol=THETA_ATOL)
    diff = np.max(np.abs(sharded["theta"] - single["theta"]))
    print(f"theta_L max |diff|: {diff!r} (rtol {THETA_RTOL}, "
          f"atol {THETA_ATOL})")
    print(f"peak bytes in use: {_peak_bytes(jax.devices())}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-bank comparison")
    args = ap.parse_args(argv)

    import jax
    if args.chips == 4:
        # sharding-invariant noise draws, as in the sharded-engine tests
        jax.config.update("jax_threefry_partitionable", True)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"model: {cfg.name} ({cfg.n_layers} blocks, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab})")
    print(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 1:
        one_chip(cfg)
    else:
        four_chips(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
