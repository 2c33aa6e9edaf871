"""The sLSTM block's hand-written backward against autodiff.

`slstm_forward` runs its time scan under a `custom_vjp`; its reference
here is the plain `lax.scan` over `_slstm_cell`, differentiated by JAX.
Both must agree in f32 for the recurrent weight, the bias, the input
weight and the input: plainly, per record under `vmap`, and under
`jax.checkpoint`.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import xlstm
from repro.models.layers import rms_norm

S, D, H = 13, 32, 2          # hd = 16; S is not a power of two


def _reference_forward(p, x, cfg):
    """slstm_forward as a plain scan over the cell, left to autodiff."""
    B, S_, d = x.shape
    zin = jnp.einsum("bsd,dhkg->bshkg", x, p.w_in)
    st0 = xlstm.init_slstm_state(B, cfg)
    _, hs = jax.lax.scan(lambda st, z: xlstm._slstm_cell(p, z, st), st0,
                         zin.swapaxes(0, 1))
    y = rms_norm(hs.swapaxes(0, 1).reshape(B, S_, d).astype(x.dtype), p.norm)
    a, g = jnp.split(jnp.einsum("bsd,df->bsf", y, p.w_up), 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(a) * g, p.w_down)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("xlstm-125m").reduced(), d_model=D,
                              n_heads=H, n_kv_heads=H, head_dim=D // H)
    p = xlstm.init_slstm(jax.random.PRNGKey(0), cfg, jnp.float32)
    # a livelier recurrence and bias than at init, so each gradient is
    # far from zero
    p = p._replace(r=3.0 * p.r, b=p.b + 0.5 * jax.random.normal(
        jax.random.PRNGKey(1), p.b.shape))
    return cfg, p


def _grads(forward, cfg, p, x, w, mode):
    """d loss / d (r, b, w_in, x)."""
    def loss(p, x, w):
        return jnp.sum(forward(p, x, cfg) * w)

    if mode == "vmap":        # per record, as the `example` granularity
        f = jax.vmap(jax.grad(lambda p, x, w: loss(p, x[None], w[None]),
                              (0, 1)), in_axes=(None, 0, 0))
    elif mode == "checkpoint":
        f = jax.grad(jax.checkpoint(loss), (0, 1))
    else:
        f = jax.grad(loss, (0, 1))
    dp, dx = jax.jit(f)(p, x, w)
    return {"r": dp.r, "b": dp.b, "w_in": dp.w_in, "x": dx}


@pytest.mark.parametrize("mode,batch", [("plain", 1), ("plain", 3),
                                        ("vmap", 3), ("checkpoint", 3)])
def test_slstm_backward_matches_autodiff(setup, mode, batch):
    cfg, p = setup
    kx, kw = jax.random.split(jax.random.PRNGKey(2 + batch))
    x = jax.random.normal(kx, (batch, S, D), jnp.float32)
    w = jax.random.normal(kw, (batch, S, D), jnp.float32)
    np.testing.assert_allclose(xlstm.slstm_forward(p, x, cfg),
                               _reference_forward(p, x, cfg),
                               rtol=1e-5, atol=1e-6)
    got = _grads(xlstm.slstm_forward, cfg, p, x, w, mode)
    want = _grads(_reference_forward, cfg, p, x, w, mode)
    for name in ("r", "b", "w_in", "x"):
        assert got[name].shape == want[name].shape, name
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 1e-3, name
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                   atol=1e-5 * scale, err_msg=name)
