"""Pallas TPU kernels: fused owner-bank row encode/decode (int8 / fp8).

The owner bank is the deep engine's dominant state: `(N_owners, P)` model
copies that the fused multi-round scan carries through every round.
Quantized storage (1 byte/element + per-row f32 scales) cuts the resident
bytes and the scan's loop-carry traffic ~4x vs f32; these kernels make the
row round-trip cheap enough to sit inside the scan body:

  absmax (pass 1)  — blockwise partial |x| maxima; the caller combines
                     them into the per-row scale, exactly like
                     dp_clip_noise's sqnorm pass.
  encode (pass 2)  — ONE fused pass that stochastically rounds the row
                     onto the int8/fp8 grid AND writes the quantization
                     error row (the error-feedback residual), so EF costs
                     no extra read of the f32 row.
  decode           — codes * scale in one pass.

The stochastic-rounding bits are pre-generated uint32s from jax.random
(the round key), same contract as the Laplace bits in dp_clip_noise: the
privacy-adjacent RNG stays the library one. The numeric transform is
imported from ref.py so kernel and jnp oracle can never drift.

Layout: rows are flattened and padded to (rows, 1024) blocks of
(block_rows, 1024) — 8x128-aligned VMEM tiles. Zero padding is inert for
absmax and is sliced off after encode/decode. (int8/fp8 VMEM tiles want
32 sublanes; block_rows defaults far above that.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bank_codec.ref import (CODE_DTYPES, decode_fp8_ref,
                                          decode_int8_ref, encode_fp8_ref,
                                          encode_int8_ref, row_scales_ref)
from repro.kernels.dp_clip_noise.kernel import PARTIAL_TILE

LANES = 1024


def _absmax_kernel(x_ref, o_ref):
    o_ref[...] = jnp.full(PARTIAL_TILE,
                          jnp.max(jnp.abs(x_ref[...].astype(jnp.float32))))


def _encode_kernel(x_ref, u_ref, s_ref, q_ref, e_ref, *, fmt):
    enc = encode_int8_ref if fmt == "int8" else encode_fp8_ref
    codes, err = enc(x_ref[...], u_ref[...], s_ref[0, 0])
    q_ref[...] = codes
    e_ref[...] = err


def _decode_kernel(q_ref, s_ref, o_ref, *, fmt):
    dec = decode_int8_ref if fmt == "int8" else decode_fp8_ref
    o_ref[...] = dec(q_ref[...], s_ref[0, 0])


def absmax_2d(x: jax.Array, *, block_rows: int = 256,
              interpret: bool = False) -> jax.Array:
    """Blockwise partial absmax; caller takes the max. x: (R, LANES) f32."""
    R, C = x.shape
    assert C == LANES and R % block_rows == 0, (x.shape, block_rows)
    nb = R // block_rows
    partial = pl.pallas_call(
        _absmax_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(PARTIAL_TILE, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (nb * PARTIAL_TILE[0], PARTIAL_TILE[1]), jnp.float32),
        interpret=interpret,
        name="bank_absmax",
    )(x)
    return jnp.max(partial)


def row_scale_2d(x: jax.Array, qmax: float, *, block_rows: int = 256,
                 interpret: bool = False) -> jax.Array:
    """Per-row scale from the blockwise absmax pass (same floor as the
    oracle's row_scales_ref)."""
    return jnp.maximum(absmax_2d(x, block_rows=block_rows,
                                 interpret=interpret), 1e-30) / qmax


def encode_2d(x: jax.Array, bits: jax.Array, scale: jax.Array, fmt: str, *,
              block_rows: int = 256, interpret: bool = False):
    """Fused stochastic-round encode + error write -> (codes, err).

    x: (R, LANES) f32; bits: (R, LANES) uint32; scale: (1, 1) f32 (traced).
    """
    R, C = x.shape
    assert C == LANES and R % block_rows == 0, (x.shape, block_rows)
    assert bits.shape == x.shape
    code_dtype = CODE_DTYPES[fmt]
    grid = (R // block_rows,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_encode_kernel, fmt=fmt),
        grid=grid,
        in_specs=[blk, blk, one],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((R, C), code_dtype),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        interpret=interpret,
        name="bank_encode",
    )(x, bits, scale)


def decode_2d(codes: jax.Array, scale: jax.Array, fmt: str, *,
              block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """decode(codes) * scale in one pass. codes: (R, LANES) int8 /
    e4m3fn-pattern uint8; scale: (1, 1) f32."""
    R, C = codes.shape
    assert C == LANES and R % block_rows == 0, (codes.shape, block_rows)
    grid = (R // block_rows,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, fmt=fmt),
        grid=grid,
        in_specs=[blk, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
        name="bank_decode",
    )(codes, scale)


__all__ = ["LANES", "absmax_2d", "row_scale_2d", "encode_2d", "decode_2d",
           "row_scales_ref"]
