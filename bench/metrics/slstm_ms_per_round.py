"""Device time of the sLSTM blocks (ms a round): the operations under the
model's `model.slstm` scope in the window, forward and backward
(`transpose(jvp(model.slstm))`), averaged over the devices, over the
rounds traced. Nothing from a program without the round's scopes."""
from bench import program_trace


def read(ctx):
    s = program_trace.scope_seconds(ctx, "model.slstm")
    return None if s is None else 1e3 * s / ctx.rounds
