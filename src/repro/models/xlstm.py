"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, parallelizable)
and sLSTM (scalar memory, strictly sequential scan).

mLSTM maps onto the generalized SSD scan in `repro.models.ssm`:
    state C_t = f_t C_{t-1} + i_t k_t v_t^T   ->   ld = log f, g = i, k/q per head
with a normalizer obtained by augmenting v with a ones-channel, and
`y = num / max(|den|, 1)`.

TPU adaptation (recorded in DESIGN.md): gates are *bounded* —
f = sigmoid(f_raw), i = sigmoid(i_raw) — instead of the paper's exp input
gate + running-max stabilizer. The normalizer makes the block equivalent up
to the stabilizer; bounded gates keep the chunked scan overflow-free in bf16
without carrying a per-head running max through the chunk scan.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, XLSTMConfig
from repro.models.layers import dense_init, rms_norm
from repro.models.ssm import causal_conv, causal_conv_step, ssd_chunked, ssd_step


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------
class MLSTMParams(NamedTuple):
    w_up: jax.Array      # (d, dm)
    w_z: jax.Array       # (d, dm)
    conv: jax.Array      # (K, dm)
    w_q: jax.Array       # (dm, H, N)
    w_k: jax.Array       # (dm, H, N)
    w_v: jax.Array       # (dm, H, N)   (P == N == dm // H)
    w_i: jax.Array       # (dm, H)
    w_f: jax.Array       # (dm, H)
    b_f: jax.Array       # (H,) fp32 — init positive: remember by default
    norm: jax.Array      # (dm,)
    w_down: jax.Array    # (dm, d)


class MLSTMState(NamedTuple):
    h: jax.Array         # (B, H, N, P+1) fp32 — last channel = normalizer
    conv: jax.Array      # (B, K-1, dm)


def mlstm_dims(cfg: ModelConfig):
    x = cfg.xlstm or XLSTMConfig()
    dm = int(cfg.d_model * x.mlstm_proj_factor)
    H = cfg.n_heads
    N = dm // H
    return dm, H, N


def init_mlstm(key, cfg: ModelConfig, dtype) -> MLSTMParams:
    x = cfg.xlstm or XLSTMConfig()
    dm, H, N = mlstm_dims(cfg)
    ks = jax.random.split(key, 8)
    return MLSTMParams(
        w_up=dense_init(ks[0], (cfg.d_model, dm), dtype),
        w_z=dense_init(ks[1], (cfg.d_model, dm), dtype),
        conv=dense_init(ks[2], (x.conv_kernel, dm), dtype, scale=0.5),
        w_q=dense_init(ks[3], (dm, H, N), dtype),
        w_k=dense_init(ks[4], (dm, H, N), dtype),
        w_v=dense_init(ks[5], (dm, H, N), dtype),
        w_i=dense_init(ks[6], (dm, H), dtype),
        w_f=dense_init(ks[7], (dm, H), dtype),
        b_f=3.0 * jnp.ones((H,), jnp.float32),
        norm=jnp.ones((dm,), dtype),
        w_down=dense_init(jax.random.fold_in(key, 99), (dm, cfg.d_model), dtype),
    )


def _mlstm_qkvif(p: MLSTMParams, u: jax.Array, uc: jax.Array):
    q = jnp.einsum("bse,ehn->bshn", uc, p.w_q)
    k = jnp.einsum("bse,ehn->bshn", uc, p.w_k)
    v = jnp.einsum("bse,ehn->bshn", u, p.w_v)
    i_raw = jnp.einsum("bse,eh->bsh", uc, p.w_i).astype(jnp.float32)
    f_raw = jnp.einsum("bse,eh->bsh", uc, p.w_f).astype(jnp.float32) + p.b_f
    i_g = jax.nn.sigmoid(i_raw)
    log_f = -jax.nn.softplus(-f_raw)              # log sigmoid(f_raw)
    return q, k, v, i_g, log_f


def mlstm_forward(p: MLSTMParams, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, d = x.shape
    dm, H, N = mlstm_dims(cfg)
    u = jnp.einsum("bsd,de->bse", x, p.w_up)
    z = jnp.einsum("bsd,de->bse", x, p.w_z)
    uc = jax.nn.silu(causal_conv(u, p.conv).astype(jnp.float32)).astype(x.dtype)
    q, k, v, i_g, log_f = _mlstm_qkvif(p, u, uc)
    ones = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    v_aug = jnp.concatenate([v, ones], axis=-1)           # (B,S,H,N+1)
    chunk = min(256, max(S, 8))
    y_aug, _ = ssd_chunked(v_aug, log_f, k, q, i_g, chunk=chunk)
    num, den = y_aug[..., :N].astype(jnp.float32), y_aug[..., N].astype(jnp.float32)
    y = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    y = y.reshape(B, S, dm).astype(x.dtype)
    y = rms_norm(y, p.norm) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", y, p.w_down)


def init_mlstm_state(batch: int, cfg: ModelConfig,
                     dtype=jnp.bfloat16) -> MLSTMState:
    x = cfg.xlstm or XLSTMConfig()
    dm, H, N = mlstm_dims(cfg)
    return MLSTMState(
        h=jnp.zeros((batch, H, N, N + 1), jnp.float32),
        conv=jnp.zeros((batch, x.conv_kernel - 1, dm), dtype))


def mlstm_decode(p: MLSTMParams, x: jax.Array, state: MLSTMState,
                 cfg: ModelConfig):
    B, _, d = x.shape
    dm, H, N = mlstm_dims(cfg)
    u = jnp.einsum("bsd,de->bse", x, p.w_up)
    z = jnp.einsum("bsd,de->bse", x, p.w_z)
    c_out, new_conv = causal_conv_step(state.conv.astype(u.dtype), u[:, 0], p.conv)
    uc = jax.nn.silu(c_out.astype(jnp.float32)).astype(x.dtype)[:, None]
    q, k, v, i_g, log_f = _mlstm_qkvif(p, u, uc)
    ones = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    v_aug = jnp.concatenate([v, ones], axis=-1)
    y_aug, h_new = ssd_step(state.h, v_aug[:, 0], log_f[:, 0], k[:, 0],
                            q[:, 0], i_g[:, 0])
    num = y_aug[..., :N].astype(jnp.float32)
    den = y_aug[..., N].astype(jnp.float32)
    y = (num / jnp.maximum(jnp.abs(den), 1.0)[..., None]).reshape(B, 1, dm)
    y = rms_norm(y.astype(x.dtype), p.norm) * jax.nn.silu(
        z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p.w_down)
    return out, MLSTMState(h_new, new_conv.astype(state.conv.dtype))


# ---------------------------------------------------------------------------
# sLSTM block — strictly sequential exponential-gated scalar memory
# ---------------------------------------------------------------------------
class SLSTMParams(NamedTuple):
    w_in: jax.Array      # (d, H, hd, 4)  input weights for i,f,z,o
    r: jax.Array         # (H, hd, hd, 4) per-head recurrent weights
    b: jax.Array         # (H, hd, 4) fp32
    norm: jax.Array      # (d,)
    w_up: jax.Array      # (d, 2*fs)
    w_down: jax.Array    # (fs, d)


class SLSTMState(NamedTuple):
    c: jax.Array         # (B, H, hd) fp32
    n: jax.Array
    hst: jax.Array
    m: jax.Array


def slstm_dims(cfg: ModelConfig):
    x = cfg.xlstm or XLSTMConfig()
    H = cfg.n_heads
    hd = cfg.d_model // H
    fs = int(cfg.d_model * x.slstm_proj_factor)
    return H, hd, fs


def init_slstm(key, cfg: ModelConfig, dtype) -> SLSTMParams:
    H, hd, fs = slstm_dims(cfg)
    ks = jax.random.split(key, 4)
    b = jnp.zeros((H, hd, 4), jnp.float32).at[..., 1].set(3.0)  # f-bias > 0
    return SLSTMParams(
        w_in=dense_init(ks[0], (cfg.d_model, H, hd, 4), dtype),
        r=dense_init(ks[1], (H, hd, hd, 4), dtype, scale=0.3),
        b=b,
        norm=jnp.ones((cfg.d_model,), dtype),
        w_up=dense_init(ks[2], (cfg.d_model, 2 * fs), dtype),
        w_down=dense_init(ks[3], (fs, cfg.d_model), dtype),
    )


def _slstm_gates(i_raw, f_raw, z_raw, o_raw, st: SLSTMState) -> SLSTMState:
    """The cell's pointwise part, from its four gate pre-activations (f32)."""
    log_f = -jax.nn.softplus(-f_raw)             # log sigmoid — stabilized f
    m_new = jnp.maximum(log_f + st.m, i_raw)
    i_t = jnp.exp(i_raw - m_new)
    f_t = jnp.exp(log_f + st.m - m_new)
    z_t = jnp.tanh(z_raw)
    o_t = jax.nn.sigmoid(o_raw)
    c_new = f_t * st.c + i_t * z_t
    n_new = f_t * st.n + i_t
    h_new = o_t * c_new / jnp.maximum(n_new, 1e-6)
    return SLSTMState(c_new, n_new, h_new, m_new)


def _slstm_cell(p: SLSTMParams, zin: jax.Array, st: SLSTMState) -> Tuple[SLSTMState, jax.Array]:
    """zin: (B,H,hd,4) pre-activations from input; recurrent added here."""
    rec = jnp.einsum("bhd,hdkg->bhkg", st.hst.astype(jnp.float32),
                     p.r.astype(jnp.float32))
    pre = zin.astype(jnp.float32) + rec + p.b
    st2 = _slstm_gates(*[pre[..., j] for j in range(4)], st)
    return st2, st2.hst


# The time scan below holds every gate-indexed array gate-major, the
# (hd, 4) pair flattened to 4*hd with hd minor: a trailing gate axis of 4
# would be padded to a whole lane tile on TPU. It has a hand-written
# backward so that no parameter cotangent rides in the loop carry: the
# reverse scan carries only the state cotangents and emits each step's
# pre-activation cotangent; dr and db are contracted once, after it.
_HIGHEST = jax.lax.Precision.HIGHEST


def _slstm_run(r, b, zin, save: bool):
    """r (H, hd, 4*hd), b (H, 4*hd), zin (S, B, H, 4*hd), all f32.
    Returns hs (S, B, H, hd) f32 and, if `save`, the backward's residuals:
    each step's pre-activations and its incoming state."""
    _, B, H, _ = zin.shape
    z = jnp.zeros((B, H, r.shape[1]), jnp.float32)
    st0 = SLSTMState(z, z, z, jnp.full_like(z, -1e30))

    def step(st, z_t):
        rec = jnp.einsum("bhd,hdn->bhn", st.hst, r, precision=_HIGHEST)
        pre = z_t + rec + b
        st2 = _slstm_gates(*jnp.split(pre, 4, axis=-1), st)
        return st2, ((st2.hst, pre, st) if save else st2.hst)

    return jax.lax.scan(step, st0, zin)[1]


@jax.custom_vjp
def _slstm_scan(r, b, zin):
    return _slstm_run(r, b, zin, save=False)


def _slstm_scan_fwd(r, b, zin):
    hs, pre, prev = _slstm_run(r, b, zin, save=True)
    return hs, (r, pre, prev)


def _slstm_scan_bwd(res, dhs):
    r, pre, prev = res

    def step(dst, xs):
        dh_t, pre_t, st = xs
        _, vjp = jax.vjp(
            lambda p, s: _slstm_gates(*jnp.split(p, 4, axis=-1), s), pre_t, st)
        dpre, dprev = vjp(dst._replace(hst=dst.hst + dh_t))
        dh = jnp.einsum("bhn,hdn->bhd", dpre, r, precision=_HIGHEST)
        return dprev._replace(hst=dh), dpre

    z = jnp.zeros(dhs.shape[1:], jnp.float32)
    _, dpre = jax.lax.scan(step, SLSTMState(z, z, z, z), (dhs, pre, prev),
                           reverse=True)
    dr = jnp.einsum("sbhd,sbhn->hdn", prev.hst, dpre, precision=_HIGHEST)
    return dr, dpre.sum((0, 1)), dpre


_slstm_scan.defvjp(_slstm_scan_fwd, _slstm_scan_bwd)


def slstm_forward(p: SLSTMParams, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, d = x.shape
    H, hd, fs = slstm_dims(cfg)
    # gate-major layouts, built once per call
    zin = jnp.einsum("bsd,dhkg->sbhgk", x, p.w_in).reshape(S, B, H, 4 * hd)
    r = p.r.transpose(0, 1, 3, 2).reshape(H, hd, 4 * hd)
    b = p.b.swapaxes(1, 2).reshape(H, 4 * hd)
    hs = _slstm_scan(*(a.astype(jnp.float32) for a in (r, b, zin)))
    y = hs.swapaxes(0, 1).reshape(B, S, d).astype(x.dtype)
    y = rms_norm(y, p.norm)
    up = jnp.einsum("bsd,df->bsf", y, p.w_up)
    a, g = jnp.split(up, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(a.astype(jnp.float32)
                                                 ).astype(x.dtype) * g, p.w_down)


def init_slstm_state(batch: int, cfg: ModelConfig) -> SLSTMState:
    H, hd, _ = slstm_dims(cfg)
    z = jnp.zeros((batch, H, hd), jnp.float32)
    return SLSTMState(z, z, z, jnp.full((batch, H, hd), -1e30, jnp.float32))


def slstm_decode(p: SLSTMParams, x: jax.Array, st: SLSTMState, cfg: ModelConfig):
    B, _, d = x.shape
    H, hd, fs = slstm_dims(cfg)
    zin = jnp.einsum("bsd,dhkg->bshkg", x, p.w_in)[:, 0]
    st2, h = _slstm_cell(p, zin, st)
    y = h.reshape(B, 1, d).astype(x.dtype)
    y = rms_norm(y, p.norm)
    up = jnp.einsum("bsd,df->bsf", y, p.w_up)
    a, g = jnp.split(up, 2, axis=-1)
    out = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(a.astype(jnp.float32)
                                                ).astype(x.dtype) * g, p.w_down)
    return out, st2
