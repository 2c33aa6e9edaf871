"""Pallas TPU flash attention (forward): blockwise online softmax.

Grid (B, H, n_q_blocks, n_kv_blocks); the kv axis is the minor-most grid
dimension, so VMEM scratch (m, l, acc) persists across kv iterations for a
fixed q block (TPU grids iterate sequentially). Causal and sliding-window
masks supported; out-of-window / beyond-causal kv blocks are skipped with
pl.when so the MXU never sees them.

Block sizes are multiples of (8, 128) to match TPU tiling; hd is padded to
128 by ops.py when needed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, n_kv: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(2)
    q_start = i * bq
    k_start = j * bk

    # static-shape guards are not possible for dynamic program ids; use
    # pl.when to skip fully-masked blocks.
    beyond_causal = causal and (k_start > q_start + bq - 1)
    # (evaluated as traced bool)
    run = jnp.asarray(True)
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window is not None:
        run = run & (q_start - (k_start + bk - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         bq: int = 256, bk: int = 256,
                         scale: Optional[float] = None,
                         interpret: bool = False) -> jax.Array:
    """q,k,v: (B, H, S, hd) with matching H (GQA expanded by ops.py).

    `scale` defaults to hd**-0.5 of the given (possibly padded) hd; callers
    that zero-pad hd must pass the unpadded scale.
    """
    B, H, S, hd = q.shape
    Skv = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, Skv)
    assert S % bq == 0 and Skv % bk == 0, (S, bq, Skv, bk)
    nq, nk = S // bq, Skv // bk
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=nk)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
