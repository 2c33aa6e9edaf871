"""Share of the traced window in which the device ran no operation (%),
averaged over the devices."""
from bench import traces


def read(ctx):
    if not ctx.trace.devices:
        return None
    return 100.0 * traces.idle_share(ctx.trace)
