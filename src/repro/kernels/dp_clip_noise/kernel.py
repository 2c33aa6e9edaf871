"""Pallas TPU kernel: fused gradient scale + Laplace-noise add (eq. 4),
plus the whole-round `dp_round` kernel for the flat-buffer engine.

The DP response Qbar = clip(g) + Laplace(b) is HBM-bound: the naive
implementation makes three passes over the gradient (norm, scale, add
noise). The fused kernel does the scale-and-noise in ONE pass: it consumes
pre-generated uniform random bits (threefry bits from jax.random — kept
outside so the privacy-critical RNG stays the library one), converts them
to Laplace via inverse-CDF in VMEM, and writes g*clip_scale + b*lap.

The squared-norm reduction (pass 1) is also provided as a blockwise kernel
(partial sums per block, combined by the caller) so the full privatization
is 2 HBM passes instead of 3+.

`dp_round` goes further for flat-packed models: the paper's whole inertia
round past the gradient — group-mean, Laplace add (eq. 4), the owner and
learner updates (eqs. 5/7, regularizer gradient included), and the
theta_max projection — is elementwise in the flat buffer, so one kernel
streams theta_bar + the accumulated clipped gradient once and writes both
updated buffers: ONE HBM pass instead of the ~7 tree_map passes of the
pytree path.

Layout: gradients are flattened and padded to (rows, 1024) fp32 blocks of
(block_rows, 1024) — 8x128-aligned VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dp_clip_noise.ref import laplace_from_bits

LANES = 1024
# A partial reduction (sqnorm here, absmax in bank_codec) fills one (8, 128)
# tile with its block's scalar: the smallest output block the TPU lowering
# accepts.
PARTIAL_TILE = (8, 128)


def _scale_noise_kernel(g_ref, u_ref, cs_ref, ns_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    lap = laplace_from_bits(u_ref[...])
    cs = cs_ref[0, 0]
    ns = ns_ref[0, 0]
    o_ref[...] = (g * cs + ns * lap).astype(o_ref.dtype)


def _dp_round_kernel(tb_ref, acc_ref, u_ref, gn_ref, ns_ref, w_ref,
                     ol_ref, oi_ref, *, sigma, lr_own, lr_l, inv_2n,
                     theta_max):
    """One block of the fused inertia round (eqs. 4-5-7 + projection).

    tb = theta_bar (eq. 6, precomputed: the gradient was taken at it);
    acc = sum of per-group clipped gradients. In-block:

        q     = acc * gain + noise_scale * Laplace(bits)      (eq. 4)
        g_reg = sigma * tb                                    (grad of g)
        oi    = Pi[ tb - lr_own * (g_reg/(2N) + w * q) ]      (eq. 5)
        ol    = Pi[ tb - lr_L * g_reg ]                       (eq. 7)

    sigma/lr_own/lr_l/inv_2n/theta_max are compile-time constants; the
    per-round traced scalars (group-mean gain, Theorem-1 noise scale, and
    the owner weight w = n_i/n) arrive as (1,1) refs.
    """
    tb = tb_ref[...].astype(jnp.float32)
    acc = acc_ref[...].astype(jnp.float32)
    lap = laplace_from_bits(u_ref[...])
    q = acc * gn_ref[0, 0] + ns_ref[0, 0] * lap
    g_reg = sigma * tb
    oi_ref[...] = jnp.clip(tb - lr_own * (g_reg * inv_2n + w_ref[0, 0] * q),
                           -theta_max, theta_max).astype(oi_ref.dtype)
    ol_ref[...] = jnp.clip(tb - lr_l * g_reg,
                           -theta_max, theta_max).astype(ol_ref.dtype)


def _sqnorm_kernel(g_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.full(PARTIAL_TILE, jnp.sum(g * g))


def scale_noise_2d(g: jax.Array, bits: jax.Array, clip_scale: jax.Array,
                   noise_scale: jax.Array, *, block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    """g: (R, LANES) fp32; bits: (R, LANES) uint32; scalars as (1,1) f32."""
    R, C = g.shape
    assert C == LANES and R % block_rows == 0, (g.shape, block_rows)
    grid = (R // block_rows,)
    return pl.pallas_call(
        _scale_noise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), g.dtype),
        interpret=interpret,
        name="scale_noise",
    )(g, bits, clip_scale, noise_scale)


def dp_round_2d(tb: jax.Array, acc: jax.Array, bits: jax.Array,
                gain: jax.Array, noise_scale: jax.Array, w: jax.Array, *,
                sigma: float, lr_own: float, lr_l: float, n_owners: int,
                theta_max: float, block_rows: int = 256,
                interpret: bool = False):
    """Whole inertia round on (R, LANES) blocks -> (new_L, new_i).

    tb/acc: (R, LANES) f32; bits: (R, LANES) uint32; gain/noise_scale/w:
    traced scalars as (1,1) f32. The remaining round constants are baked
    into the kernel at trace time.
    """
    R, C = tb.shape
    assert C == LANES and R % block_rows == 0, (tb.shape, block_rows)
    assert acc.shape == tb.shape and bits.shape == tb.shape
    grid = (R // block_rows,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kern = functools.partial(_dp_round_kernel, sigma=sigma, lr_own=lr_own,
                             lr_l=lr_l, inv_2n=1.0 / (2 * n_owners),
                             theta_max=theta_max)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[blk, blk, blk, one, one, one],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        interpret=interpret,
        name="dp_round",
    )(tb, acc, bits, gain, noise_scale, w)


def sqnorm_2d(g: jax.Array, *, block_rows: int = 256,
              interpret: bool = False) -> jax.Array:
    """Blockwise partial squared norms; caller sums. g: (R, LANES) fp32."""
    R, C = g.shape
    assert C == LANES and R % block_rows == 0
    nb = R // block_rows
    partial = pl.pallas_call(
        _sqnorm_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(PARTIAL_TILE, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * PARTIAL_TILE[0], PARTIAL_TILE[1]),
                                       jnp.float32),
        interpret=interpret,
        # the compiled call is named after the kernel, and the partial-sum
        # fusion below names it as its operand: a profile's name match on
        # "sqnorm" (bench/work/stages/clip_norm.py) would take that fusion
        # too, so this name leaves it out
        name="squared_norm",
    )(g)
    return jnp.sum(partial[::PARTIAL_TILE[0], 0])
