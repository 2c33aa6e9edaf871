"""Host time of `Federation.run_rounds` per traced dispatch (ms): the
program's own `run_rounds` spans in the window, summed, over the
benchmark's dispatches. Nothing from a program without the span."""
from bench import program_trace


def read(ctx):
    spans = program_trace.in_window(program_trace.of(ctx).spans,
                                    ctx.trace.window, "run_rounds")
    n = program_trace.dispatches(ctx.trace)
    if not spans or not n:
        return None
    return 1e3 * sum(b - a for _, a, b in spans) * 1e-9 / n
