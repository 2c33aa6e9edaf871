"""Share of the window's operation time under none of the round's
`fed.*` scopes (%): what no scope covers, such as copies XLA inserts, the
loop carry and the scan's control. Loops and calls are left out, their
time being their body's. Nothing from a program without the scopes."""
from bench import program_trace


def read(ctx):
    s = program_trace.unscoped_share(ctx)
    return None if s is None else 100.0 * s
