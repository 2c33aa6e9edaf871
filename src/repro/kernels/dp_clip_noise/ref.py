"""Pure-jnp oracle for the dp_clip_noise kernel (bit-exact transform)."""
from __future__ import annotations

import jax.numpy as jnp


def u01_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits -> uniform [0, 1) from the top 24 bits. The shifted
    value fits an int32 exactly, and the TPU compiler has no direct
    uint32 -> float32 conversion, so the cast goes through int32."""
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
            * (1.0 / (1 << 24)))


def laplace_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    v = u01_from_bits(bits) - 0.5
    return -jnp.sign(v) * jnp.log1p(
        -2.0 * jnp.abs(jnp.clip(v, -0.4999999, 0.4999999)))


def scale_noise_ref(g: jnp.ndarray, bits: jnp.ndarray, clip_scale,
                    noise_scale) -> jnp.ndarray:
    lap = laplace_from_bits(bits)
    return (g.astype(jnp.float32) * clip_scale + noise_scale * lap
            ).astype(g.dtype)


def sqnorm_ref(g: jnp.ndarray) -> jnp.ndarray:
    gf = g.astype(jnp.float32)
    return jnp.sum(gf * gf)


def dp_round_ref(tb: jnp.ndarray, acc: jnp.ndarray, bits: jnp.ndarray,
                 gain, noise_scale, w, *, sigma, lr_own, lr_l, n_owners,
                 theta_max):
    """Oracle for the fused dp_round kernel (bit-exact transform)."""
    tbf = tb.astype(jnp.float32)
    q = acc.astype(jnp.float32) * gain + noise_scale * laplace_from_bits(bits)
    g_reg = sigma * tbf
    new_i = jnp.clip(tbf - lr_own * (g_reg * (1.0 / (2 * n_owners)) + w * q),
                     -theta_max, theta_max)
    new_l = jnp.clip(tbf - lr_l * g_reg, -theta_max, theta_max)
    return new_l, new_i
