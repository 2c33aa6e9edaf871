"""Plain reference of xlstm-125m's loss, in jax.numpy.

It imports nothing of the program. The layers follow the repository's
definitions, which depart from the published ones where
`xlstm-125m.json` says so: the mLSTM in its parallel (quadratic) form,
the sLSTM one step at a time. `bench/reference.py` finds this file by the
configuration's name and calls `lm_loss`.
"""
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def rms(x, w, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def causal_conv(x, w):
    """Depthwise causal conv: out[t] = sum_j x[t - (K-1) + j] * w[j]."""
    K, S = w.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(K):
        shift = K - 1 - j
        xs = jnp.pad(x, ((shift, 0), (0, 0)))[:S]
        out = out + xs * w[j]
    return out


def decay(F, t_idx, s_idx):
    """exp(F[t] - F[s]) for s <= t, else 0: (T, S, H)."""
    d = F[t_idx][:, None, :] - F[s_idx][None, :, :]
    causal = (t_idx[:, None] >= s_idx[None, :])[..., None]
    return jnp.exp(jnp.where(causal, d, -jnp.inf))


def mlstm(p, x, dm, H):
    """mLSTM with sigmoid gates and a |.|>=1 normaliser, in its
    parallel (quadratic) form over one sequence x: (S, d)."""
    S = x.shape[0]
    u = x @ p.w_up
    z = x @ p.w_z
    uc = silu(causal_conv(u, p.conv))
    q = jnp.einsum("se,ehn->shn", uc, p.w_q)
    k = jnp.einsum("se,ehn->shn", uc, p.w_k)
    v = jnp.einsum("se,ehn->shn", u, p.w_v)
    ig = jax.nn.sigmoid(uc @ p.w_i)                               # (S, H)
    logf = jax.nn.log_sigmoid(uc @ p.w_f + p.b_f)
    F = jnp.cumsum(logf, axis=0)
    t = jnp.arange(S)
    w = jnp.einsum("thn,shn->tsh", q, k) * decay(F, t, t) * ig[None]
    num = jnp.einsum("tsh,shn->thn", w, v)
    den = jnp.sum(w, axis=1)
    y = (num / jnp.maximum(jnp.abs(den), 1.0)[..., None]).reshape(S, dm)
    return (rms(y, p.norm) * silu(z)) @ p.w_down


def slstm(p, x, H):
    """sLSTM: exponential input gate with the max stabiliser, sigmoid
    forget gate in log space, one step at a time."""
    S, d = x.shape
    hd = d // H
    zin = jnp.einsum("sd,dhkg->shkg", x, p.w_in)

    def step(st, z_t):
        c, n, h, m = st
        pre = z_t + jnp.einsum("hd,hdkg->hkg", h, p.r) + p.b
        i_raw, f_raw, z_raw, o_raw = (pre[..., g] for g in range(4))
        logf = jax.nn.log_sigmoid(f_raw)
        m_new = jnp.maximum(logf + m, i_raw)
        i_t = jnp.exp(i_raw - m_new)
        f_t = jnp.exp(logf + m - m_new)
        c = f_t * c + i_t * jnp.tanh(z_raw)
        n = f_t * n + i_t
        h = jax.nn.sigmoid(o_raw) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    zero = jnp.zeros((H, hd), x.dtype)
    m0 = jnp.full((H, hd), -1e30, x.dtype)
    _, hs = jax.lax.scan(step, (zero, zero, zero, m0), zin)
    y = rms(hs.reshape(S, d), p.norm)
    a, g = jnp.split(y @ p.w_up, 2, axis=-1)
    return (gelu_tanh(a) * g) @ p.w_down


def lm_loss(params, tokens, labels, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence. The residual
    blocks carry no pre-norm."""
    x = params["embed"][tokens]
    dm = int(m["d_model"] * m["xlstm"].get("mlstm_proj_factor", 2.0))
    for blk in params["blocks"]:
        if "slstm" in blk:
            x = x + jax.checkpoint(slstm, static_argnums=2)(
                blk["slstm"], x, m["n_heads"])
        else:
            x = x + jax.checkpoint(mlstm, static_argnums=(2, 3))(
                blk["mlstm"], x, dm, m["n_heads"])
    logits = rms(x, params["ln_f"], m.get("norm_eps", 1e-5)) @ params["unembed"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
