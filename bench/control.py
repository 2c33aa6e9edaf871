"""Readings that set the upper ends of a cell's limits.

    python bench/control.py --workload <cell> --seeds 11 12 13

For each seed it builds the cell's check dispatch (weights, owner
sequence, records, keys) and runs the plain reference in the program's
place: as it is (float32), as the control (bfloat16, the next precision
down), and, where a round holds more than one record, with half of each
batch left out and the mean taken over the rest. It prints the compared
numbers of the control and of the fault against the reference, one JSON
line per seed. A state left unchanged reads 1 on `change_gap` by
construction and needs no run. The benchmark's own runs never run this;
it needs a TPU like they do.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, devices=None) -> dict:
    """Compared numbers of the control and of the half-batch fault,
    each against the float32 reference, on one seed."""
    import jax
    import jax.numpy as jnp

    from bench import harness
    from bench.cell import model_config
    from bench.data import Traffic, round_key
    from bench.weights import weight_fn, weight_key
    from repro.models import build_model

    model = build_model(model_config(cell.model), remat=False)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
    N = cell.n_owners
    traffic = Traffic(seed, N, [cell.records(i) for i in range(N)],
                      cell.seq, cell.batch, cell.model["vocab"])
    seq = traffic.check_schedule(cell.rounds, harness.CHECK_FIRST)
    batches = traffic.batches_for(seq)
    weights, wkey = weight_fn(shapes), weight_key(seed)

    def follow(**kw):
        return harness.reference_changes(cell, shapes, weights, wkey, seq,
                                         batches, round_key(seed, 0), **kw)

    ref = follow()
    out = {"seed": seed}
    cases = [("control_bf16", {"dtype": jnp.bfloat16})]
    if cell.batch >= 2:          # a batch of one has no half to leave out
        cases.append(("half_batch", {"half_batch": True}))
    for name, kw in cases:
        got = follow(**kw)
        nums = harness.compare(got, ref, [0], [0])
        nums.pop("ledger_gap")
        nums["max_grad_norm"] = got["max_grad_norm"].tolist()
        out[name] = nums
    out["reference_max_grad_norm"] = ref["max_grad_norm"].tolist()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.cell import load_cell
    cell = load_cell(args.workload, BENCH)
    try:
        harness.require_chips(cell)
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(BENCH)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
