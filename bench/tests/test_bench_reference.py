"""The plain reference's parts on their own: its compiled round, which
scans a round's records, gives what a loop over them in Python gives,
the Laplace draws are unit Laplace, and the ledger serves an owner up to
its horizon."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.harness import load_module
from bench.tests import tiny_cell
from bench.weights import weight_fn, weight_key

BENCH = tiny_cell.BENCH


def _tiny():
    from bench.cell import model_config
    from repro.models import build_model
    m = tiny_cell.MODELS["ssm"]
    model = build_model(model_config(m), remat=False)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32))
    loss = load_module(BENCH / "configs" / "xlstm-125m.py").lm_loss
    return m, shapes, loss


def test_batched_records_match_one_at_a_time():
    m, shapes, loss = _tiny()
    rnd = reference.Round(n_owners=2, horizon=10, clip=1.0, sigma=0.01,
                          theta_max=100.0, target_lr=0.05,
                          records=[10, 12], epsilons=[1.0, 2.0])
    ref = reference.Reference(m, loss, shapes, rnd)
    L = ref.layout
    theta = L.flat(weight_fn(shapes)(weight_key(2**31 + 3)))
    rng = np.random.default_rng(7)
    toks = rng.integers(0, m["vocab"], size=(3, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=-1)
    with jax.default_matmul_precision("highest"):
        acc, top = ref._grads(theta, jnp.asarray(toks), jnp.asarray(labels))
        want, norms = np.zeros(L.size), []
        for t, lab in zip(toks, labels):
            g = np.asarray(L.flat(jax.grad(loss)(L.unflat(theta), t, lab, m)))
            n = np.sqrt(np.sum(g.astype(np.float64) ** 2))
            norms.append(n)
            want += g * min(1.0, 1.0 / n)
    assert float(top) == pytest.approx(max(norms), rel=1e-5)
    np.testing.assert_allclose(np.asarray(acc), want, rtol=1e-4,
                               atol=1e-6 * np.abs(want).max())


def test_laplace_draws_are_unit_laplace():
    L = reference.Layout.of({"w": jax.ShapeDtypeStruct((300_000,),
                                                       jnp.float32)})
    key = jnp.asarray([1, 2], jnp.uint32)
    x = np.asarray(reference.laplace(key, L, jnp.float32))
    assert x.shape == (300_000,) and np.all(np.isfinite(x))
    np.testing.assert_array_equal(
        x, np.asarray(reference.laplace(key, L, jnp.float32)))
    assert np.mean(np.abs(x)) == pytest.approx(1.0, abs=0.01)   # E|X| = 1
    assert np.mean(x) == pytest.approx(0.0, abs=0.01)


def test_laplace_draws_are_the_padded_blocks_bits():
    # the program draws its noise over whole (256, 1024) blocks
    L = reference.Layout.of({"w": jax.ShapeDtypeStruct((300_001,),
                                                       jnp.float32)})
    rows = -(-L.size // (256 * 1024)) * 256
    key = jnp.asarray([7, 2**31 + 5], jnp.uint32)
    bits = np.asarray(jax.random.bits(key, (rows, 1024), jnp.uint32)
                      ).reshape(-1)[:L.size]
    np.testing.assert_array_equal(
        bits, np.asarray(jax.random.bits(key, (L.size,), jnp.uint32)))
    u = bits >> 8
    v = np.clip(u.astype(np.float32) * np.float32(2.0 ** -24) - 0.5,
                -0.4999999, 0.4999999)
    want = -np.sign(v) * np.log1p(-2.0 * np.abs(v))
    got = np.asarray(reference.laplace(key, L, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_laplace_refuses_the_legacy_threefry():
    L = reference.Layout.of({"w": jax.ShapeDtypeStruct((1000,),
                                                       jnp.float32)})
    with jax.threefry_partitionable(False):
        with pytest.raises(ValueError, match="partitionable"):
            reference.laplace(jnp.asarray([1, 2], jnp.uint32), L,
                              jnp.float32)


def test_ledger_serves_up_to_the_horizon():
    seqs = [np.array([0, 0, 1]), np.array([0, 1, 2, 0])]
    granted, refused = reference.ledger(seqs, 3, cap=2)
    assert granted.tolist() == [2, 2, 1]
    assert refused.tolist() == [2, 0, 0]
