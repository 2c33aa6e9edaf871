"""Pallas TPU kernel: DP-FTRL tree-noise node refresh + per-round delta.

The binary-counter update for one leaf increment is elementwise over the
(P,)-flat node buffer at every level, so one kernel streams the owner's
(depth, P) node row exactly once: it converts pre-generated uniform bits
to the fresh Laplace node (inverse CDF in VMEM — the same lawful draw as
the dp_clip_noise kernels), subtracts the retired levels from the fresh
draw, zeroes them, writes the fresh level, and emits the injected delta.
Which levels retire/refresh depends only on the (1, 1) leaf count, never
on the data, so the level loop unrolls statically (depth ~ log2(T)).

Layout: nodes ride as (depth, R, 1024) with blocks of
(depth, block_rows, 1024) — the whole level axis stays resident in VMEM
per block, so keep block_rows SMALL: in/out node blocks plus bits and
delta cost (2*depth + 2) * block_rows * 4 KB; the default 64 is ~5.5 MB
at depth 10, comfortably under the ~16 MB VMEM budget where the
dp_clip_noise default of 256 would blow it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dp_clip_noise.ref import laplace_from_bits

LANES = 1024


def _tree_delta_kernel(nodes_ref, u_ref, cnt_ref, ns_ref, delta_ref,
                       out_ref, *, depth):
    t1 = cnt_ref[0, 0] + 1
    zeta = ns_ref[0, 0] * laplace_from_bits(u_ref[...])
    acc = zeta
    for lvl in range(depth):
        rem = jax.lax.rem(t1, jnp.int32(1 << (lvl + 1)))
        retired = rem == 0
        fresh = rem == jnp.int32(1 << lvl)
        nd = nodes_ref[lvl].astype(jnp.float32)
        acc = acc - jnp.where(retired, nd, jnp.zeros_like(nd))
        out_ref[lvl] = jnp.where(fresh, zeta,
                                 jnp.where(retired, jnp.zeros_like(nd), nd))
    delta_ref[...] = acc


def tree_delta_2d(nodes, bits, count, noise_scale, *, block_rows: int = 64,
                  interpret=False):
    """nodes (depth>=1, R, LANES) f32, bits (R, LANES) uint32,
    count/noise_scale (1, 1) -> (delta (R, LANES), new_nodes like nodes)."""
    depth, rows, cols = nodes.shape
    assert cols == LANES and rows % block_rows == 0, (nodes.shape, block_rows)
    assert depth >= 1, "depth-0 trees bypass the kernel (ops.tree_delta_row)"
    kern = functools.partial(_tree_delta_kernel, depth=depth)
    node_spec = pl.BlockSpec((depth, block_rows, LANES), lambda i: (0, i, 0))
    row_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        kern,
        grid=(rows // block_rows,),
        in_specs=[node_spec, row_spec, one, one],
        out_specs=[row_spec, node_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.float32),
                   jax.ShapeDtypeStruct((depth, rows, cols), jnp.float32)],
        interpret=interpret,
        name="tree_delta",
    )(nodes, bits, count, noise_scale)
