"""Model FLOP/s utilisation of the whole round (%): the operations the
model needs per round (work/configs/<config>.py) times the rounds of the
traced window, over the window's length, the chips and their bf16 peak."""


def read(ctx):
    try:
        work = ctx.work()
    except FileNotFoundError:          # a configuration with no work count
        return None
    c = ctx.cell
    flops = work.flops_per_round(c.model, c.seq, c.batch) * ctx.rounds
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
