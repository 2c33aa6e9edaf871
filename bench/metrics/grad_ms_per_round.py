"""Device time of the model's gradient at theta_bar, with the unpack of
theta_bar and the pack of the gradient (ms a round): the operations under
the round's `fed.grad` scope in the window, averaged over the devices,
over the rounds traced. Nothing from a program without the round's
scopes."""
from bench import program_trace


def read(ctx):
    s = program_trace.scope_seconds(ctx, "fed.grad")
    return None if s is None else 1e3 * s / ctx.rounds
