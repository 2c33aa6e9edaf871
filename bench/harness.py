"""One run of one cell: build the federation, warm it up, measure a
window of dispatches, check the result against the plain reference.

The window drives `Federation.run_rounds` as a user would, each dispatch
in four steps inside the clock: draw the owner sequence, build the
stacked batches, call `run_rounds`, read the round metrics. The first
dispatch of set-up is the check dispatch: the reference follows its
rounds once the window has closed.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

import numpy as np

from bench.cell import Cell, model_config
from bench.data import Traffic, round_key

CHECK_FIRST = 3          # rounds of the check dispatch the reference follows
TRACE_DISPATCHES = 3     # dispatches in a traced window, at most
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(bench_dir: Path, kind: str) -> Dict[str, Any]:
    """The published peaks of `kind`; a device missing from the table is
    an error, never a default."""
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(has {sorted(table)})")
    return table[kind]


def require_chips(cell: Cell) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


def enable_compile_cache(bench_dir: Path) -> str:
    """JAX's persistent cache at `$JAX_COMPILATION_CACHE_DIR`, else at the
    fixed `.jax_cache/` of the checkout; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(bench_dir.parent / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts lowerings (a compile or a persistent-cache read) in the
    process, so the window can prove it holds none."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.n += 1


class Spans:
    """The benchmark's own host spans (prep, dispatch, read_metrics,
    wait), written into the profiler's trace while it runs."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        if not self.tracing:
            yield
            return
        with jax.profiler.TraceAnnotation(name):
            yield


class CellRun:
    """The cell's federation, built from its configuration and traffic,
    and driven one dispatch at a time."""

    def __init__(self, cell: Cell, seed: int, spans: Spans):
        import jax
        import jax.numpy as jnp

        from repro.federation import (DataOwner, Federation,
                                      FederationConfig, PrivatizerConfig)
        from repro.models import build_model
        from bench.weights import weight_fn, weight_key

        self.cell, self.seed, self.spans = cell, seed, spans
        t = cell.traffic
        mcfg = model_config(cell.model)
        # checkpointed, as a deployment trains; it changes no math, and
        # an xLSTM stack has nothing to checkpoint
        model = build_model(mcfg, remat=True)
        self.shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
        self.weights = weight_fn(self.shapes)
        self.weight_key = weight_key(seed)
        N = cell.n_owners
        owners = [DataOwner(n=cell.records(i), epsilon=cell.epsilon(i),
                            xi=t["clip_norm"]) for i in range(N)]
        self.fed = Federation(owners, FederationConfig.from_target_lr(
            t["target_lr"], n_owners=N, horizon=cell.horizon,
            sigma=t["sigma"], theta_max=t["theta_max"]))

        def loss_fn(p, b):
            return model.loss(p, b)[0]

        self.fed.make_step(
            loss_fn, pack_params=True, bank_dtype=jnp.dtype(t["bank_dtype"]),
            privatizer=PrivatizerConfig(
                xi=t["clip_norm"], granularity="microbatch",
                n_microbatches=cell.batch, fused_kernel=True),
            donate=True)
        self.state = self.fed.init_state(self.weights(self.weight_key))
        self.traffic = Traffic(seed, N, [cell.records(i) for i in range(N)],
                               cell.seq, cell.batch, cell.model["vocab"])
        self.n_dispatch = 0
        self.seqs: List[np.ndarray] = []

    def dispatch(self, owner_seq: Optional[np.ndarray] = None) -> Dict:
        """One dispatch of K rounds, the four steps of a user's loop."""
        import jax
        span = self.spans
        with span("prep"):
            if owner_seq is None:
                owner_seq = self.traffic.schedule(self.cell.rounds)
            host = self.traffic.batches_for(owner_seq)
            batches = jax.device_put(host)
            seq = jax.device_put(owner_seq)
            key = jax.device_put(round_key(self.seed, self.n_dispatch))
        with span("dispatch"):
            self.state, ms = self.fed.run_rounds(self.state, batches, seq,
                                                 key=key)
        with span("read_metrics"):
            out = {k: np.asarray(ms[k]) for k in
                   ("refused", "owner", "max_grad_norm")}
        self.seqs.append(np.asarray(owner_seq))
        self.n_dispatch += 1
        out["batches"] = host
        return out

    def wait(self):
        import jax
        with self.spans("wait"):
            jax.block_until_ready(self.state)


def program_changes(run: CellRun, owners: np.ndarray) -> np.ndarray:
    """Per-leaf norms of what the check dispatch changed in the bank rows
    of `owners` (the owners of its first rounds, which no later round of
    the dispatch touches): each row against its bf16 start."""
    import jax
    import jax.numpy as jnp
    from bench.reference import Layout
    L = Layout.of(run.shapes)

    def norms(bank, owners, key):
        start = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                       run.weights(key))

        def row_norms(i):
            row = jax.lax.dynamic_index_in_dim(bank, i, 0, keepdims=False)
            return L.leaf_norms(row, start)

        return jax.lax.map(row_norms, owners)

    return np.asarray(jax.jit(norms)(run.state.bank, jnp.asarray(owners),
                                     run.weight_key))


def reference_changes(cell: Cell, shapes, weights, weight_key, seq, batches,
                      key, dtype=None, half_batch=False):
    """The reference's numbers for the first CHECK_FIRST rounds of the
    check dispatch: the largest per-record gradient norm of each round
    and the per-leaf change of each round's bank row. The model's loss
    is the configuration's plain one, `configs/<config>.py`."""
    import jax
    import jax.numpy as jnp
    from bench.reference import Reference, Round
    t = cell.traffic
    N = cell.n_owners
    rnd = Round(n_owners=N, horizon=cell.horizon, clip=t["clip_norm"],
                sigma=t["sigma"], theta_max=t["theta_max"],
                target_lr=t["target_lr"],
                records=[cell.records(i) for i in range(N)],
                epsilons=[cell.epsilon(i) for i in range(N)])
    loss = load_module(cell.bench_dir / "configs"
                       / f"{cell.config_name}.py").lm_loss
    ref = Reference(cell.model, loss, shapes, rnd,
                    dtype=dtype or jnp.float32, half_batch=half_batch)
    L = ref.layout
    first = seq[:CHECK_FIRST]
    out = ref.follow(jax.jit(lambda k: L.flat(weights(k)))(weight_key), first,
                     {k: v[:CHECK_FIRST] for k, v in batches.items()}, key,
                     rounds=len(seq))
    row_norms = jax.jit(lambda r, r0: L.leaf_norms(
        r.astype(jnp.float32) - r0.astype(jnp.float32)))
    d_rows = np.stack([np.asarray(row_norms(out["rows"][int(o)],
                                            out["row0"])) for o in first])
    return {"max_grad_norm": out["max_grad_norm"], "d_rows": d_rows}


def change_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf: |norm_prog - norm_ref| over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    floor = np.maximum(ref, np.median(ref, axis=-1, keepdims=True))
    return float(np.max(np.abs(prog - ref) / floor))


def compare(prog: Dict, ref: Dict, ledger_prog, ledger_ref) -> Dict:
    """The numbers `correct` is decided on."""
    k = len(ref["max_grad_norm"])
    g = np.max(np.abs(prog["max_grad_norm"][:k] - ref["max_grad_norm"])
               / ref["max_grad_norm"])
    change = change_gap(prog["d_rows"], ref["d_rows"])
    ledger = int(np.sum(np.abs(np.asarray(ledger_prog)
                               - np.asarray(ledger_ref))))
    return {"grad_norm_gap": float(g), "change_gap": float(change),
            "ledger_gap": ledger}


def limits_for(cell: Cell) -> Dict[str, float]:
    """The cell's limits (limits/<cell>.json), one per compared number."""
    with open(cell.bench_dir / "limits" / f"{cell.name}.json") as f:
        limits = json.load(f)["limits"]
    return {k: float(v["limit"]) for k, v in limits.items()}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        devices: list, out_dir: Optional[Path] = None) -> Dict[str, Any]:
    """One run of `cell`: set-up, window, check. Returns the result line
    (and, when traced, what `per_layer` reads under "_trace")."""
    import jax

    compiles = Compiles()
    spans = Spans()

    def phase(name):
        print(f"set-up: {name} at {time.perf_counter() - t0:.2f} s, "
              f"{compiles.n} lowerings", file=sys.stderr, flush=True)

    r = CellRun(cell, seed, spans)
    K = cell.rounds
    phase("federation built")

    # set-up: the check dispatch, which compiles
    check_seq = r.traffic.check_schedule(K, CHECK_FIRST)
    first = r.dispatch(check_seq)
    phase("check dispatch")
    check_batches = first["batches"]
    prog = {"max_grad_norm": first["max_grad_norm"].astype(np.float64),
            "d_rows": program_changes(r, check_seq[:CHECK_FIRST])}
    phase("check read")
    # the check dispatch ran every program the window runs (on one chip;
    # a mesh compiles its second dispatch again and would need a warm-up)
    r.wait()
    setup_s = time.perf_counter() - t0

    # the window
    profile_dir = None
    if trace:
        profile_dir = tempfile.mkdtemp(prefix="profile-",
                                       dir=str(out_dir) if out_dir else None)
        spans.tracing = True
        jax.profiler.start_trace(profile_dir)
    compiles_before = compiles.n
    attempted = granted = refused = 0
    n = 0
    ends = []             # the window's clock as each dispatch returns
    start = time.perf_counter()
    while True:
        m = r.dispatch()
        attempted += K
        refused += int(m["refused"].sum())
        granted += int((~m["refused"]).sum())
        n += 1
        elapsed = time.perf_counter() - start
        ends.append(elapsed)
        if elapsed >= seconds or (trace and n >= TRACE_DISPATCHES):
            break
    r.wait()
    window_s = time.perf_counter() - start
    window_compiles = compiles.n - compiles_before
    if trace:
        jax.profiler.stop_trace()
        spans.tracing = False
    peak = memory_peak(devices)
    print(f"window: seconds of each dispatch {np.diff([0.0] + ends).tolist()}",
          file=sys.stderr)

    # the ledger, reconciled, against the reference's count
    ledger = r.fed.reconcile(r.state)
    from bench.reference import ledger as ref_ledger
    g_ref, f_ref = ref_ledger(r.seqs, cell.n_owners, cell.horizon)
    led_prog = ([ledger[i]["responses"] for i in range(cell.n_owners)]
                + [ledger[i]["refused"] for i in range(cell.n_owners)])
    led_ref = list(g_ref) + list(f_ref)

    shapes, weights, wkey = r.shapes, r.weights, r.weight_key
    del r
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_changes(cell, shapes, weights, wkey, check_seq,
                            check_batches, round_key(seed, 0))
    print(f"reference: {time.perf_counter() - t_ref:.2f} s; max per-record "
          f"gradient norm of each round, program "
          f"{prog['max_grad_norm'].tolist()}, reference "
          f"{ref['max_grad_norm'].tolist()}", file=sys.stderr)
    numbers = compare(prog, ref, led_prog, led_ref)
    numbers["window_compiles"] = window_compiles
    limits = limits_for(cell)
    limits["window_compiles"] = 0
    correct = all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
                  for k in limits)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}

    d0 = devices[0]
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": refused,
        "metrics": {},
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak}}
    if not trace:
        result["metrics"] = {
            "rounds_per_s": {"value": granted / window_s, "unit": "rounds/s"},
            "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        from bench.reference import Layout
        result["_trace"] = {"profile_dir": profile_dir, "rounds": attempted,
                            "n_params": Layout.of(shapes).size}
    result["checks"] = checks
    return result


def per_layer(cell: Cell, result: Dict[str, Any], devices) -> None:
    """Fill a traced run's metrics, device busy time and breakdown from
    its trace."""
    from bench import traces as tr
    info = result.pop("_trace")
    t = tr.load_xplane(info["profile_dir"])
    ctx = Context(cell=cell, trace=t, rounds=info["rounds"],
                  n_params=info["n_params"],
                  peak=peaks(cell.bench_dir, devices[0].device_kind),
                  chips=len(t.devices) or 1)
    metrics = {}
    for name in cell.metric_names(trace=True):
        spec = next(m for m in cell.per_layer if m["name"] == name)
        mod = load_module(cell.bench_dir / "metrics" / f"{name}.py")
        v = mod.read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": spec["unit"]}
    result["metrics"] = metrics
    result["device"]["busy_s"] = tr.busy_s(t)
    result["device"]["window_s"] = t.window_s
    result["breakdown"] = {"device_ops": tr.top_ops(t),
                           "idle_gaps": tr.idle_gaps(t)}


class Context:
    """What a per-layer metric reader gets: the trace, the cell, the
    rounds traced, the chips, P and the peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def work(self):
        return load_module(self.cell.bench_dir / "work" / "configs"
                           / f"{self.cell.config_name}.py")

    def stage(self, name: str):
        return load_module(self.cell.bench_dir / "work" / "stages"
                           / f"{name}.py")


def emit(result: Dict[str, Any], out=sys.stdout, err=sys.stderr) -> None:
    """Print the compared numbers last on stderr, then the result line
    last on stdout, with the checks as its last key."""
    for k, v in result["checks"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=err)
    line = {k: v for k, v in result.items() if not k.startswith("_")}
    line["checks"] = line.pop("checks")
    print(json.dumps(line), file=out)
