"""Kernel-path microbenches (CPU): pure-jnp reference implementations at
small scale + the Pallas kernels in interpret mode for correctness-parity
timing. Device timing comes from the chip benchmark (`bench/run.py`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.kernels.flash_attention.ref import attention_ref
from repro.models.attention import blockwise_attention
from repro.models.ssm import ssd_chunked


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    kq, kv, kk, kl, kg = jax.random.split(key, 5)
    B, S, H, hd = 2, 512, 4, 64
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    pos = jnp.arange(S)

    f_block = jax.jit(lambda q: blockwise_attention(
        q, q, q, q_positions=pos, kv_positions=pos, kv_chunk=128))
    _, us = timed(lambda: jax.block_until_ready(f_block(q)))
    rows.append((f"kernels/blockwise_attention_jnp/B{B}S{S}", us,
                 f"flops={4*B*S*S*H*hd:.3g}"))

    f_ref = jax.jit(lambda q: attention_ref(
        q.transpose(0, 2, 1, 3), q.transpose(0, 2, 1, 3),
        q.transpose(0, 2, 1, 3)))
    _, us_ref = timed(lambda: jax.block_until_ready(f_ref(q)))
    rows.append((f"kernels/attention_materialized/B{B}S{S}", us_ref,
                 "oracle"))

    v = jax.random.normal(kv, (B, S, H, hd))
    k2 = jax.random.normal(kk, (B, S, H, 16))
    ld = -jax.nn.softplus(jax.random.normal(kl, (B, S, H)))
    g = jax.nn.sigmoid(jax.random.normal(kg, (B, S, H)))
    f_ssd = jax.jit(lambda: jax.block_until_ready(
        ssd_chunked(v, ld, k2, k2, g, chunk=128)[0]))
    _, us_ssd = timed(f_ssd)
    rows.append((f"kernels/ssd_chunked_jnp/B{B}S{S}", us_ssd,
                 f"state={H*16*hd}"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_rows
    print(fmt_rows(run()))
