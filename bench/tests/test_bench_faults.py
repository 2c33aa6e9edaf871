"""A run whose timed path is broken underneath comes out not correct:
a round that returns its state unchanged, and a round that leaves half
of its batch out and takes the mean over the rest."""
import dataclasses

import jax
import pytest

from bench import harness
from bench.tests import tiny_cell


def _unchanged_state(monkeypatch):
    built = harness.CellRun.__init__

    def init(self, *a, **kw):
        built(self, *a, **kw)
        fused = jax.jit(self.fed._fused_fn.__wrapped__)

        def unchanged(state, *args):
            _, metrics = fused(state, *args)
            return state, metrics

        self.fed._fused_fn = unchanged

    monkeypatch.setattr(harness.CellRun, "__init__", init)


def _half_batch(monkeypatch):
    import repro.federation.deep as deep
    full = deep._flat_clipped_grad_acc

    def half(loss_fn, spec, pcfg, tb, batch, mesh=None):
        B = jax.tree_util.tree_leaves(batch)[0].shape[0]
        kept = jax.tree_util.tree_map(lambda a: a[:B // 2], batch)
        pcfg = dataclasses.replace(pcfg,
                                   n_microbatches=pcfg.n_microbatches // 2)
        return full(loss_fn, spec, pcfg, tb, kept, mesh)

    monkeypatch.setattr(deep, "_flat_clipped_grad_acc", half)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_broken_round_is_not_correct(tmp_path, monkeypatch, fault):
    tiny_cell.interpret_kernels(monkeypatch)
    name = tiny_cell.write(tmp_path, "ssm")
    fault(monkeypatch)
    result = tiny_cell.run(tmp_path, name)
    assert not result["correct"], result["checks"]
