"""Share of its roofline reached by the noise + inertia stage (%): the
least bytes the stage needs per round (work/stages/dp_round.py) times the
rounds traced, at the HBM peak, over the device time of the stage's
operations; averaged over the devices. Nothing when the trace names no
such operation."""
import jax.numpy as jnp

from bench import traces


def read(ctx):
    stage = ctx.stage("dp_round")
    item = jnp.dtype(ctx.cell.traffic["bank_dtype"]).itemsize
    secs = traces.op_seconds(ctx.trace, stage.OPS)
    secs = [s for s in secs.values() if s > 0]
    if not secs:
        return None
    need = (stage.least_bytes(ctx.n_params, item) * ctx.rounds
            / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * sum(need / s for s in secs) / len(secs)
