"""Pure-jnp oracle for the bank_codec kernels (bit-exact transform).

Two row codecs for the `(N_owners, P)` owner bank:

  int8 — symmetric linear code. q = floor(x/scale + u), clipped to
    [-127, 127]; decode is q * scale. `floor(v + u)` with u ~ U[0, 1) IS
    stochastic rounding (P[round up] == frac(v)), and u == 0.5 is the
    deterministic round-to-nearest used for bank init.
  fp8 — float8_e4m3fn. Stochastic rounding happens ON THE fp8 GRID: the
    two representable neighbours bracketing |x|/scale are found via uint8
    bit-pattern steps (the e4m3fn patterns of same-sign finite values are
    monotone), and the upper one is chosen with probability proportional
    to the distance from the lower. The sign rides as the top bit.

Both encoders also return the quantization error x - decode(encode(x)),
computed in f32 — the error-feedback residual the round engine folds into
the next granted update.

`u` is uniform in [0, 1) from the top 24 bits of uint32 random bits (the
same convention as dp_clip_noise's Laplace path), so the privacy-adjacent
RNG stays the jax.random stream of the round key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dp_clip_noise.ref import u01_from_bits

INT8_QMAX = 127.0
FP8_QMAX = 448.0          # largest finite float8_e4m3fn
_TINY = 1e-30             # scale floor: an all-zero row decodes to zeros


def det_bits(shape) -> jnp.ndarray:
    """The uint32 pattern whose u01 transform is exactly 0.5 — feeding
    these to either encoder makes it the deterministic round-to-nearest
    used for bank init (no key needed, reproducible)."""
    return jnp.full(shape, jnp.uint32(1) << 31, jnp.uint32)


def counter_bits(seed: jnp.ndarray, shape) -> jnp.ndarray:
    """Cheap counter-based uint32 stream: murmur3's fmix32 finalizer over
    (golden-ratio-striped counter + seed).

    Stochastic-rounding bits are NOT privacy-critical — they perturb
    storage precision, never the DP noise, which stays on the threefry
    stream — so the codec trades threefry's ~50 ops/word for ~7. The
    `seed` is a (), uint32 scalar drawn from the round key (one tiny
    threefry call per round instead of a P-element one); on TPU the
    in-kernel analogue is pltpu.prng_random_bits. Full-avalanche mixing,
    so consecutive counters give independent-looking rounding decisions.
    """
    n = 1
    for s in shape:
        n *= s
    i = jax.lax.iota(jnp.uint32, n)
    x = i * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x.reshape(shape)


def row_scales_ref(x2d: jnp.ndarray, qmax: float) -> jnp.ndarray:
    """(nb, be) f32 -> (nb,) scales = absmax/qmax, floored away from 0."""
    return jnp.maximum(jnp.max(jnp.abs(x2d.astype(jnp.float32)), axis=-1),
                       _TINY) / qmax


def encode_int8_ref(x: jnp.ndarray, bits: jnp.ndarray, scale
                    ) -> tuple:
    """-> (codes int8, err f32) with err == x - codes*scale exactly."""
    xf = x.astype(jnp.float32)
    u = u01_from_bits(bits)
    q = jnp.clip(jnp.floor(xf / scale + u), -INT8_QMAX, INT8_QMAX)
    return q.astype(jnp.int8), xf - q * scale


def decode_int8_ref(codes: jnp.ndarray, scale) -> jnp.ndarray:
    return codes.astype(jnp.float32) * scale


# The fp8 transforms below work on the e4m3fn BIT PATTERNS with ordinary
# vectorized int/float ops instead of ml_dtypes casts: XLA:CPU lowers
# float8 astype to scalar library calls (~15x slower than the int8 path,
# measured), while frexp/ldexp/floor vectorize. The magnitude patterns of
# finite e4m3fn values are monotone, so bits+1 is the next grid point —
# and since the encoder clips to FP8_QMAX (0x7E), the NaN pattern 0x7F is
# never produced. Patterns are held as int32 between the uint8 codes at
# either end: the TPU compiler has no shifts on 8-bit vectors and no
# float32 -> uint8 conversion.

def _fp8_decode_mag(b: jnp.ndarray) -> jnp.ndarray:
    """|value| of int32 e4m3fn magnitude bit patterns (sign bit 0).

    Pure integer construction (frexp/ldexp lower to scalar libm calls on
    XLA:CPU): normal = (8+m) * 2^(e-10), with the power of two built
    directly as an f32 bit pattern ((e-10)+127 biased exponent)."""
    e = b >> 3
    m = b & 7
    two_pow = jax.lax.bitcast_convert_type((e + 117) << 23, jnp.float32)
    normal = (8 + m).astype(jnp.float32) * two_pow
    subnormal = m.astype(jnp.float32) * jnp.float32(1.0 / (1 << 9))
    return jnp.where(e > 0, normal, subnormal)


def _fp8_floor_bits(a: jnp.ndarray) -> jnp.ndarray:
    """Largest e4m3fn magnitude pattern <= a (a in [0, FP8_QMAX]), int32.

    Truncates the f32 bit pattern directly: for normal e4m3 values the
    f32 fields map as e = E - 120, m = top 3 mantissa bits (truncation
    IS floor for non-negative values)."""
    ab = jax.lax.bitcast_convert_type(a, jnp.int32)
    e = ((ab >> 23) & 0xFF) - 120            # e4m3 exponent field
    m = (ab >> 20) & 0x7                     # top 3 mantissa bits
    normal = (e << 3) | m
    subnormal = jnp.floor(a * (1 << 9)).astype(jnp.int32)
    return jnp.where(a < 1.0 / (1 << 6), subnormal, normal)


def fp8_sr(y: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Stochastically round f32 onto the float8_e4m3fn grid. |y| must
    already be clipped to FP8_QMAX. Returns the uint8 BIT PATTERNS, not
    an f8-typed array: XLA:CPU scalar-emulates every op on float8 arrays
    (even select and scatter), so the codec keeps fp8 codes as raw bytes
    end to end and only materializes f32 values (`fp8_to_f32`)."""
    a = jnp.abs(y)
    lo_b = _fp8_floor_bits(a)
    hi_b = lo_b + 1
    lo = _fp8_decode_mag(lo_b)
    hi = _fp8_decode_mag(hi_b)
    p = jnp.where(a > lo, (a - lo) / (hi - lo), 0.0)
    out = jnp.where(u < p, hi_b, lo_b)
    return jnp.where(y < 0, out | 0x80, out).astype(jnp.uint8)


def fp8_to_f32(codes: jnp.ndarray) -> jnp.ndarray:
    """Vectorized e4m3fn -> f32 (signed) from uint8 bit patterns (an
    f8-typed array is accepted and viewed as bytes), bypassing astype."""
    if codes.dtype != jnp.uint8:
        codes = jax.lax.bitcast_convert_type(codes, jnp.uint8)
    b = codes.astype(jnp.int32)
    mag = _fp8_decode_mag(b & 0x7F)
    return jnp.where(b > 0x7F, -mag, mag)


def encode_fp8_ref(x: jnp.ndarray, bits: jnp.ndarray, scale) -> tuple:
    """-> (codes float8_e4m3fn, err f32)."""
    xf = x.astype(jnp.float32)
    y = jnp.clip(xf / scale, -FP8_QMAX, FP8_QMAX)
    codes = fp8_sr(y, u01_from_bits(bits))
    return codes, xf - fp8_to_f32(codes) * scale


def decode_fp8_ref(codes: jnp.ndarray, scale) -> jnp.ndarray:
    return fp8_to_f32(codes) * scale


ENCODERS = {"int8": encode_int8_ref, "fp8": encode_fp8_ref}
DECODERS = {"int8": decode_int8_ref, "fp8": decode_fp8_ref}
QMAX = {"int8": INT8_QMAX, "fp8": FP8_QMAX}
# fp8 codes are stored as raw e4m3fn bit patterns (see fp8_sr)
CODE_DTYPES = {"int8": jnp.int8, "fp8": jnp.uint8}
