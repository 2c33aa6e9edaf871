"""What the program says about itself in a traced window: its host spans
and the named scopes of its device operations.

`Federation.run_rounds` writes host spans into the profiler's trace:
`run_rounds` around the call, `host_sync` around each blocking
device-to-host read, `launch` around the call into the jitted driver. The
private round names its operations with `jax.named_scope` (`fed.grad`,
`fed.clip`, `fed.noise`, `fed.bank_read`, `fed.bank_write`, `fed.ledger`;
the model's `model.slstm`, `model.mlstm`): each HLO instruction's
`op_name` holds the path of scopes it was traced under.

Both are read from the `.xplane.pb` the harness loaded: the newest
profile under `<repo>/.bench_profile/`, which `run.py` removes only once
the per-layer metrics are read. The benchmark's own spans in that file
must give exactly the window of the trace the harness loaded, or the file
is another run's and this raises. On a TPU v5e the device operations'
events carry their HLO text and no `op_name`, and the device plane has
no name-scope line; the profiler keeps each program's HLO, metadata and
all, in its `/host:metadata` plane. So an operation's scopes are those of
its instruction's `op_name` in the HLO of the program (the device
plane's `XLA Modules` event) it ran in; an operation of a program whose
HLO the profile lacks is matched on its label. A program without these
spans or scopes (one older than them) reads as holding none: the metrics
that need them then report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Set, Tuple

from bench import traces

PROGRAM_SPANS = ("run_rounds", "host_sync", "launch")
ROUND_SCOPES = ("fed.grad", "fed.clip", "fed.noise", "fed.bank_read",
                "fed.bank_write", "fed.ledger")

Span = Tuple[str, int, int]          # (name, start, end) ns


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                # the program's host spans, by start
    # device -> its programs' runs (start, end, "name(program id)")
    modules: Dict[str, List[Tuple[int, int, str]]] = dataclasses.field(
        default_factory=dict)
    # "name(program id)" -> instruction -> op_name
    op_names: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)
    # (name, start, duration) of the device's asynchronous operations,
    # whose spans overlap the work that runs meanwhile
    async_ops: Set[Tuple[str, int, int]] = dataclasses.field(
        default_factory=set)

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        return cls([tuple(s) for s in d["program_spans"]],
                   {k: [tuple(m) for m in v]
                    for k, v in d.get("modules", {}).items()},
                   d.get("op_names", {}),
                   {tuple(a) for a in d.get("async_ops", [])})

    def op_path(self, device: str, op: traces.Op) -> str:
        """The operation's scope path: its instruction's op_name in the
        program it ran in, else its label."""
        runs = self.modules.get(device)
        if runs:
            i = bisect.bisect_right(runs, (op.start, float("inf"), "")) - 1
            if i >= 0 and op.start < runs[i][1]:
                names = self.op_names.get(runs[i][2])
                if names is not None:
                    return "/" + names.get(op.short, "")
        return op.label


def profile_file(repo: Path) -> str:
    """The newest `.xplane.pb` under `<repo>/.bench_profile/profile-*`."""
    files = glob.glob(str(repo / ".bench_profile" / "profile-*" / "**"
                          / "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no profile under {repo / '.bench_profile'}")
    return max(files, key=os.path.getmtime)


def load(path: str, window: Tuple[int, int]) -> ProgramTrace:
    """The program's host spans, its programs' runs on each device and
    their instructions' op_names in the profile at `path`, after checking
    that its benchmark spans give `window`."""
    from jax.profiler import ProfileData
    bench: List[Span] = []
    program: List[Span] = []
    modules: Dict[str, List[Tuple[int, int, str]]] = {}
    async_ops: Set[Tuple[str, int, int]] = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = sorted(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         ev.name) for ev in line.events)
                elif line.name == "Async XLA Ops":
                    async_ops.update((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns))
                                     for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                    if ev.name in traces.HOST_SPANS:
                        bench.append(span)
                    elif ev.name in PROGRAM_SPANS:
                        program.append(span)
    got = ((min(s[1] for s in bench), max(s[2] for s in bench))
           if bench else None)
    if got != tuple(window):
        raise ValueError(f"{path} gives the window {got}, the trace read "
                         f"gives {tuple(window)}: another run's profile")
    lo, hi = window
    modules = {dev: [m for m in runs if m[1] > lo and m[0] < hi]
               for dev, runs in modules.items()}
    wanted = {m[2] for runs in modules.values() for m in runs}
    return ProgramTrace(sorted(program, key=lambda s: s[1]), modules,
                        hlo_op_names(path, wanted), async_ops)


def of(ctx) -> ProgramTrace:
    """The program's spans and op names for a metric reader's context,
    read once."""
    if getattr(ctx, "program", None) is None:
        ctx.program = load(profile_file(ctx.cell.bench_dir.parent),
                           ctx.trace.window)
    return ctx.program


# ------------------------------------------ the profile's HLO, raw protobuf
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, span=None) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the protobuf message in b[span]: an int
    for a varint, the (start, end) of a length-delimited field."""
    i, hi = span or (0, len(b))
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _first(b: bytes, span, field: int):
    return next((v for f, v in _fields(b, span) if f == field), None)


def hlo_op_names(path: str, modules) -> Dict[str, Dict[str, str]]:
    """Per program in `modules` ("name(program id)", as the device plane's
    `XLA Modules` events name them): each instruction's op_name, from the
    HLO protos in the profile's `/host:metadata` plane.

    Field numbers: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5 (map entries: key 1, value 2); XEventMetadata.name 2,
    stats 5; XStat.metadata_id 1, bytes_value 6; XStatMetadata.name 2;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2."""
    b = Path(path).read_bytes()

    def text(span) -> str:
        return b[span[0]:span[1]].decode("utf-8", "replace")

    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(b):
        name = _first(b, plane, 2) if f == 1 else None
        if name is None or text(name) != "/host:metadata":
            continue
        stat_names = {}
        for f2, entry in _fields(b, plane):
            meta = _first(b, entry, 2) if f2 == 5 else None
            sname = meta and _first(b, meta, 2)
            if sname:
                stat_names[_first(b, entry, 1)] = text(sname)
        for f2, entry in _fields(b, plane):
            meta = _first(b, entry, 2) if f2 == 4 else None
            mname = meta and _first(b, meta, 2)
            if not mname or text(mname) not in modules:
                continue
            for f3, stat in _fields(b, meta):
                if (f3 == 5 and stat_names.get(_first(b, stat, 1))
                        == "Hlo Proto"):
                    proto = _first(b, stat, 6)
                    if proto:
                        out[text(mname)] = _op_names(b, proto, text)
    return out


def _op_names(b: bytes, proto, text) -> Dict[str, str]:
    """Instruction name -> op_name of every computation of an HloProto."""
    names: Dict[str, str] = {}
    module = _first(b, proto, 1)
    for f, comp in _fields(b, module) if module else ():
        for f2, ins in _fields(b, comp) if f == 3 else ():
            if f2 != 2:
                continue
            name, meta = None, None
            for f3, v in _fields(b, ins):
                if f3 == 1:
                    name = text(v)
                elif f3 == 7:
                    meta = v
            op_name = meta and _first(b, meta, 2)
            names[name] = text(op_name) if op_name else ""
    return names


# ------------------------------------------------------------ reductions
def in_window(spans: List[Span], window: Tuple[int, int],
              name: str) -> List[Span]:
    lo, hi = window
    return [s for s in spans if s[0] == name and lo <= s[1] and s[2] <= hi]


def dispatches(trace: traces.Trace) -> int:
    """The benchmark's dispatches in the window."""
    return sum(1 for s in trace.spans if s[0] == "dispatch")


# A scope is a component of a name path: `.../fed.grad/...` or, under
# jax.grad, `.../transpose(jvp(model.slstm))/...`; never an operand's name
# such as `%fed.grad.3` in an operation's HLO text.
_SCOPE = re.compile(r"[/( ]((?:fed|model)\.\w+)(?=[/)])")


def op_scopes(ctx) -> Dict[str, List[Tuple[float, frozenset]]]:
    """Per device, each operation of the window that holds the device (as
    its seconds inside the window and the scopes on its path); read once.
    Left out: loops and calls, whose time is their body's, and the spans
    of asynchronous operations (copies, slices), which overlap the work
    that runs meanwhile and would count that time twice."""
    if getattr(ctx, "op_scopes", None) is None:
        t, program = ctx.trace, of(ctx)
        lo, hi = t.window
        ctx.op_scopes = {
            dev: [((min(o.end, hi) - max(o.start, lo)) * 1e-9,
                   frozenset(_SCOPE.findall(program.op_path(dev, o))))
                  for o in ops if o.end > lo and o.start < hi
                  and o.opcode not in traces.CONTROL_FLOW
                  and (o.name, o.start, o.dur) not in program.async_ops]
            for dev, ops in t.devices.items()}
    return ctx.op_scopes


def scope_seconds(ctx, *scopes: str) -> Optional[float]:
    """Device seconds of the operations under any of `scopes`, summed
    per device and averaged over the devices; None when no operation
    carries a scope of the round (a program without them)."""
    per_dev = op_scopes(ctx)
    if not any(s & set(ROUND_SCOPES) for ops in per_dev.values()
               for _, s in ops):
        return None
    want = set(scopes)
    return sum(sum(sec for sec, s in ops if s & want)
               for ops in per_dev.values()) / len(per_dev)


def unscoped_share(ctx) -> Optional[float]:
    """Operation time under no scope of the round over all operation
    time, summed over the devices; None without any such scope."""
    per_dev = op_scopes(ctx)
    ops = [(sec, bool(s & set(ROUND_SCOPES))) for dev in per_dev.values()
           for sec, s in dev]
    total = sum(sec for sec, _ in ops)
    if not total or not any(scoped for _, scoped in ops):
        return None
    return sum(sec for sec, scoped in ops if not scoped) / total
