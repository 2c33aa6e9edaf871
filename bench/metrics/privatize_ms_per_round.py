"""Device time of the privacy engine (ms a round): per-record clipping
(`fed.clip`: squared norms, clip scales, the accumulation) and noise with
inertia (`fed.noise`: theta_bar, the noise and eqs. 5-7 pass, the refusal
masking of theta_L), in the window, averaged over the devices, over the
rounds traced. Nothing from a program without the round's scopes."""
from bench import program_trace


def read(ctx):
    s = program_trace.scope_seconds(ctx, "fed.clip", "fed.noise")
    return None if s is None else 1e3 * s / ctx.rounds
