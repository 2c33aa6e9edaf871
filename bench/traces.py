"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` turns the JAX profiler's `.xplane.pb` into a `Trace`:
device operations per device, and the benchmark's own host spans, on one
clock. The reductions below work on a `Trace` alone, so a recorded trace
(tests/fixtures) checks them without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("prep", "dispatch", "read_metrics", "wait")
# Lines of a device plane that hold whole modules or steps, not operations.
NON_OP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Name Scope",
                "Framework Ops", "Source code", "SparseCore")


CONTROL_FLOW = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")


@dataclasses.dataclass
class Op:
    name: str          # the operation's name in the trace
    label: str         # name plus what the trace says of it (op, long name)
    start: int         # ns
    dur: int           # ns

    @property
    def end(self) -> int:
        return self.start + self.dur

    @property
    def short(self) -> str:
        """The instruction's name without its HLO text."""
        return self.name.split(" = ")[0].lstrip("%")

    @property
    def opcode(self) -> str:
        head = self.label.split(" = ", 1)
        m = _OPCODE.search(head[1]) if len(head) > 1 else None
        return m.group(1) if m else ""


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]                 # device -> operations
    spans: List[Tuple[str, int, int]]            # (name, start, end) ns
    window: Tuple[int, int]                      # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [Op(*o) for o in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["spans"]], tuple(d["window"]))


def load_xplane(log_dir: str, spans: Sequence[str] = HOST_SPANS) -> Trace:
    """Read the newest trace under `log_dir`. The window runs from the
    first to the last of the benchmark's host spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in NON_OP_LINES:
                    continue
                for ev in line.events:
                    extra = []
                    for k, v in ev.stats:
                        if k in ("long_name", "tf_op", "hlo_op",
                                 "kernel_name", "name"):
                            extra.append(str(v))
                    ops.append(Op(ev.name, " ".join([ev.name] + extra),
                                  int(ev.start_ns), int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    host.sort(key=lambda s: s[1])
    if not host:
        raise ValueError("the trace holds none of the benchmark's spans")
    window = (host[0][1], max(s[2] for s in host))
    devices = {d: sorted(ops, key=lambda o: o.start)
               for d, ops in devices.items() if ops}
    return Trace(devices, host, window)


# ------------------------------------------------------------ reductions
def clipped(ops: Iterable[Op], window: Tuple[int, int]
            ) -> List[Tuple[int, int]]:
    lo, hi = window
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    total = 0
    for ops in trace.devices.values():
        total += sum(b - a for a, b in union(clipped(ops, trace.window)))
    return total * 1e-9 / len(trace.devices)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def matching(ops: Iterable[Op], patterns: Sequence[str]) -> List[Op]:
    """Operations whose label matches one of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [o for o in ops if any(r.search(o.label) for r in rx)]


def op_seconds(trace: Trace, patterns: Sequence[str]) -> Dict[str, float]:
    """Per device: summed duration of the operations whose label holds
    one of `patterns`, inside the window."""
    out = {}
    for dev, ops in trace.devices.items():
        out[dev] = sum(b - a for a, b in clipped(matching(ops, patterns),
                                                 trace.window)) * 1e-9
    return out


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time, by name, averaged
    over the devices. Loops and calls that hold other operations are
    left out: their time is their body's."""
    totals: Dict[str, float] = {}
    for ops in trace.devices.values():
        for a, b, o in ((max(o.start, trace.window[0]),
                         min(o.end, trace.window[1]), o) for o in ops):
            if b > a and o.opcode not in CONTROL_FLOW:
                totals[o.short] = totals.get(o.short, 0.0) + (b - a) * 1e-9
    k = max(len(trace.devices), 1)
    return [[name, t / k] for name, t in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def host_span_at(trace: Trace, t: int) -> str:
    """The innermost benchmark span open at time t (the latest to start)."""
    open_ = [s for s in trace.spans if s[1] <= t < s[2]]
    return max(open_, key=lambda s: s[1])[0] if open_ else "none"


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest stretches of the window in which the first device ran
    nothing, each named by the host span open at its middle."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    busy = union(clipped(trace.devices[dev], trace.window))
    gaps, cur = [], trace.window[0]
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if trace.window[1] > cur:
        gaps.append((cur, trace.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_span_at(trace, (a + b) // 2), (b - a) * 1e-9]
            for a, b in gaps[:n]]


def span_seconds(trace: Trace, name: str) -> List[float]:
    return [(b - a) * 1e-9 for s, a, b in trace.spans if s == name]


def load(path: Path) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
