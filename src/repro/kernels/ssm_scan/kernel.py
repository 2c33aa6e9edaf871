"""Pallas TPU kernel: intra-chunk SSD contraction (Mamba2 / mLSTM).

Per (batch, head, chunk) the kernel computes, entirely in VMEM:
    cum      = inclusive cumsum of log-decay within the chunk        (Q,)
    y_intra  = tril((q k^T) * exp(cum_i - cum_j)) @ (g * v)          (Q,P)
    h_add    = (k * exp(tot - cum) * g)^T @ v                        (N,P)
    dec_tot  = exp(tot)                                              (1,)
The inter-chunk recurrence (h = dec_tot*h + h_add; y += q*exp(cum) @ h_prev)
is a tiny sequential jnp scan in ops.py — the quadratic work lives here.

Blocks: Q<=256, N,P<=128 -> every operand tile fits VMEM (Q*N + Q*P + Q*Q
fp32 ~ 0.5 MB at Q=256, N=P=64).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(v_ref, k_ref, q_ref, ld_ref, g_ref,
                      y_ref, hadd_ref, cum_ref, tot_ref):
    v = v_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)
    k = k_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    q = q_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    ld = ld_ref[0, 0, 0].astype(jnp.float32)     # (Q, 1)
    g = g_ref[0, 0, 0].astype(jnp.float32)       # (Q, 1)

    cum = jnp.cumsum(ld, axis=0)                 # (Q, 1) inclusive
    tot = cum[-1:, :]                            # (1, 1)
    Q = v.shape[0]

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # mask BEFORE exp: above-diagonal differences are positive and overflow
    dec = jnp.exp(jnp.where(jj <= ii, cum - cum.T, -jnp.inf))
    gv = g * v                                   # (Q, P)
    y = jax.lax.dot_general(qk * dec, gv, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    w = jnp.exp(tot - cum)                       # (Q, 1)
    h_add = jax.lax.dot_general(k * w, gv, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N,P)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    hadd_ref[0, 0, 0] = h_add.astype(hadd_ref.dtype)
    cum_ref[0, 0, 0] = cum.astype(cum_ref.dtype)
    tot_ref[0, 0, 0] = tot[0].astype(tot_ref.dtype)


def ssd_chunk_scan(v: jax.Array, k: jax.Array, q: jax.Array, ld: jax.Array,
                   g: jax.Array, *, interpret: bool = False):
    """All inputs chunked: v (B,H,nc,Q,P); k,q (B,H,nc,Q,N);
    ld,g (B,H,nc,Q,1). Returns (y_intra, h_add, cum, tot)."""
    B, H, nc, Q, P = v.shape
    N = k.shape[-1]
    grid = (B, H, nc)
    def sp(*dims):
        return pl.BlockSpec((1, 1, 1) + dims,
                            lambda b, h, c: (b, h, c, 0, 0))
    y, hadd, cum, tot = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[sp(Q, P), sp(Q, N), sp(Q, N), sp(Q, 1), sp(Q, 1)],
        out_specs=[sp(Q, P), sp(N, P), sp(Q, 1),
                   pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (b, h, c, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, Q, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, 1), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_chunk_scan",
    )(v, k, q, ld, g)
    return y, hadd, cum, tot
