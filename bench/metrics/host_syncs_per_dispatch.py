"""Blocking device-to-host reads per traced dispatch: the program's
`host_sync` spans inside its `run_rounds` spans in the window, over the
benchmark's dispatches. It checks at run time the host-sync contract of
`Federation.run_rounds`. Nothing from a program without the spans."""
from bench import program_trace


def read(ctx):
    spans = program_trace.of(ctx).spans
    calls = program_trace.in_window(spans, ctx.trace.window, "run_rounds")
    n = program_trace.dispatches(ctx.trace)
    if not calls or not n:
        return None
    syncs = [s for s in spans if s[0] == "host_sync"
             and any(a <= s[1] and s[2] <= b for _, a, b in calls)]
    return len(syncs) / n
