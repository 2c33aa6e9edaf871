"""Share of its roofline reached by per-record clipping (%): 4 P bytes
per record (work/stages/clip_norm.py), records = rounds x batch, at the
HBM peak, over the device time of the squared-norm operations; averaged
over the devices. Nothing when the trace names no such operation."""
from bench import traces


def read(ctx):
    stage = ctx.stage("clip_norm")
    secs = traces.op_seconds(ctx.trace, stage.OPS)
    secs = [s for s in secs.values() if s > 0]
    if not secs:
        return None
    need = (stage.least_bytes(ctx.n_params) * ctx.rounds * ctx.cell.batch
            / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * sum(need / s for s in secs) / len(secs)
