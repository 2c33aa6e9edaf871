"""Device time of the owner bank (ms a round): the row gather
(`fed.bank_read`) and the masked row write (`fed.bank_write`), in the
window, averaged over the devices, over the rounds traced. Nothing from a
program without the round's scopes."""
from bench import program_trace


def read(ctx):
    s = program_trace.scope_seconds(ctx, "fed.bank_read", "fed.bank_write")
    return None if s is None else 1e3 * s / ctx.rounds
