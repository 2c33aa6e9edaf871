"""Host preparation per dispatch (ms): the benchmark's own span around
the owner draw, the stacking of the round batches and their transfer, as
the trace's host plane holds it, averaged over the traced dispatches."""
from bench import traces


def read(ctx):
    prep = traces.span_seconds(ctx.trace, "prep")
    if not prep:
        return None
    return 1e3 * sum(prep) / len(prep)
