"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Without `--trace` the last stdout line reports the cell's end-to-end
metrics (rounds/s over the window, peak HBM, set-up seconds); with
`--trace 1` its per-layer metrics, read from a profiler trace of a short
window. Either way the run is checked against the plain reference and
the compared numbers are printed beside their limits, last on stderr and
as the result line's last key. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""
import time

T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)        # keep this directory's modules out of top level
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.cell import load_cell
    cell = load_cell(args.workload, BENCH)
    try:
        devices = harness.require_chips(cell)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(BENCH)
    out_dir = ROOT / ".bench_profile"     # profiles; removed once read
    if args.trace:
        out_dir.mkdir(exist_ok=True)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T0, devices, out_dir=out_dir)
    if args.trace:
        profile = result["_trace"]["profile_dir"]
        try:
            harness.per_layer(cell, result, devices)
        finally:
            shutil.rmtree(profile, ignore_errors=True)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
