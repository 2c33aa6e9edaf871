"""Serving-path microbench: batched one-token decode steps/sec on CPU for
every assigned architecture (reduced configs)."""
from __future__ import annotations

import time
import zlib

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.models import build_model


def run(archs=None, batch: int = 2, steps: int = 3):
    rows = []
    key = jax.random.PRNGKey(0)
    for arch in archs or list_archs():
        cfg = get_config(arch).reduced()
        model = build_model(cfg, remat=False, moe_mode="ragged")
        k_init, k_frames = jax.random.split(jax.random.fold_in(
            key, zlib.crc32(arch.encode()) & 0x7FFFFFFF))
        params = model.init(k_init, jnp.float32)
        cache = model.init_cache(batch, 32, dtype=jnp.float32)
        if cfg.family == "audio":
            frames = jax.random.normal(k_frames,
                                       (batch, cfg.enc_seq, cfg.d_model))
            cache = model.prime_cross_cache(params, cache, frames)
        step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos))
        toks = jnp.zeros((batch, 1), jnp.int32)
        logits, cache = step(params, cache, toks, jnp.int32(0))  # compile
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for t in range(1, steps + 1):
            logits, cache = step(params, cache, toks, jnp.int32(t))
        jax.block_until_ready(logits)
        us = (time.perf_counter() - t0) * 1e6 / steps
        rows.append((f"serving/{arch}/decode_step", us,
                     f"tok_per_s={batch/(us/1e6):.1f};family={cfg.family}"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_rows
    print(fmt_rows(run()))
