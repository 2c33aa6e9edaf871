"""jit'd wrapper: privatize a gradient PYTREE with the fused Pallas kernel.

    noisy = dp_privatize_tree(grads, key, xi=..., noise_scale=..., interpret=...)

Two HBM passes total: (1) blockwise squared-norm partials -> global norm ->
clip factor; (2) fused scale+Laplace-add. The Laplace bits come from
jax.random (threefry) so the DP guarantee rides on the library RNG.

The traced-scalar entry points accept ``interpret`` as True (Pallas
interpreter — kernel debugging), False (compiled Pallas — TPU), or the
string ``"oracle"``: the kernel's pure-jnp transform from ``ref.py``,
executed directly on the unpadded arrays. The oracle is the production
backend off-TPU (no interpreter plumbing, no block padding); its noise
stream differs from the kernel's (unpadded draw shape), which is lawful
under the same statistical-equivalence contract as the kernel itself.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dp_clip_noise.kernel import (LANES, dp_round_2d,
                                                scale_noise_2d, sqnorm_2d)

tmap = jax.tree_util.tree_map


def _pack(leaf: jax.Array, block_rows: int) -> Tuple[jax.Array, int]:
    flat = leaf.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    per_block = block_rows * LANES
    pad = (-n) % per_block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), n


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def dp_privatize_tree(grads: Any, key, xi: float, noise_scale: float, *,
                      block_rows: int = 256, interpret: bool = False) -> Any:
    """Clip the tree to global norm xi, add Laplace(noise_scale) noise."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    packed = [_pack(leaf, block_rows) for leaf in leaves]

    sq = sum(sqnorm_2d(p, block_rows=block_rows, interpret=interpret)
             for p, _ in packed)
    norm = jnp.sqrt(sq)
    clip = jnp.minimum(1.0, xi / jnp.maximum(norm, 1e-12))
    cs = clip.reshape(1, 1).astype(jnp.float32)
    ns = jnp.full((1, 1), noise_scale, jnp.float32)

    keys = jax.random.split(key, len(leaves))
    out = []
    for (p, n), leaf, k in zip(packed, leaves, keys):
        bits = jax.random.bits(k, p.shape, jnp.uint32)
        y = scale_noise_2d(p, bits, cs, ns, block_rows=block_rows,
                           interpret=interpret)
        out.append(y.reshape(-1)[:n].reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def on_every_device(fn, mesh):
    """`fn` run whole on every device of `mesh`.

    GSPMD cannot partition a Mosaic (compiled Pallas) kernel, so on a
    multi-device mesh the kernel runs inside a shard_map over replicated
    operands: each device computes what the one-device program would.
    No mesh, or a mesh of one device, returns `fn` as it is."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax.sharding import PartitionSpec
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def dp_round_flat(tb: jax.Array, acc: jax.Array, key, gain, noise_scale,
                  w, *, sigma: float, lr_own: float, lr_l: float,
                  n_owners: int, theta_max: float, block_rows: int = 256,
                  interpret: bool = False,
                  mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Whole inertia round on a (P,) flat buffer -> (new_L, new_i).

    Fuses group-mean (`gain`), the Laplace add (eq. 4), the eq. (5)/(7)
    inertia updates and the theta_max projection into ONE HBM pass over the
    padded 2-D view of the buffer. `gain`, `noise_scale` and `w` may be
    traced scalars (scan-body safe); the structural round constants are
    baked into the kernel. The Laplace bits come from jax.random (threefry)
    converted in-kernel by inverse CDF — a DIFFERENT lawful draw than
    jax.random.laplace, so this backend is statistically (not bitwise)
    equivalent to the jnp path: the same contract as fused_scale_noise_tree.
    `mesh` (the engine's device mesh, if any) runs the kernel through
    `on_every_device`.
    """
    if interpret == "oracle":
        from repro.kernels.dp_clip_noise.ref import dp_round_ref
        bits = jax.random.bits(key, tb.shape, jnp.uint32)
        return dp_round_ref(tb, acc, bits, gain, noise_scale, w,
                            sigma=sigma, lr_own=lr_own, lr_l=lr_l,
                            n_owners=n_owners, theta_max=theta_max)
    (p_tb, n) = _pack(tb, block_rows)
    (p_acc, _) = _pack(acc, block_rows)
    bits = jax.random.bits(key, p_tb.shape, jnp.uint32)
    gn = jnp.asarray(gain, jnp.float32).reshape(1, 1)
    ns = jnp.asarray(noise_scale, jnp.float32).reshape(1, 1)
    wv = jnp.asarray(w, jnp.float32).reshape(1, 1)
    kernel = functools.partial(
        dp_round_2d, sigma=sigma, lr_own=lr_own, lr_l=lr_l,
        n_owners=n_owners, theta_max=theta_max, block_rows=block_rows,
        interpret=interpret)
    new_l, new_i = on_every_device(kernel, mesh)(p_tb, p_acc, bits, gn, ns,
                                                 wv)
    return new_l.reshape(-1)[:n], new_i.reshape(-1)[:n]


# --------- traced-scalar entry points for in-graph (scan-body) use ---------
# dp_privatize_tree above is a jit boundary of its own; the deep path's
# fused multi-round driver instead calls these INSIDE its lax.scan body,
# where xi / noise_scale arrive as traced per-owner scalars gathered from
# the mechanism's scales array.

def fused_sqnorm_tree(tree: Any, *, block_rows: int = 256,
                      interpret=False, mesh=None) -> jax.Array:
    """Global squared L2 norm of a pytree via the blockwise Pallas pass
    (run through `on_every_device` on `mesh`)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if interpret == "oracle":
        from repro.kernels.dp_clip_noise.ref import sqnorm_ref
        return sum(sqnorm_ref(leaf) for leaf in leaves)
    kernel = on_every_device(functools.partial(
        sqnorm_2d, block_rows=block_rows, interpret=interpret), mesh)
    return sum(kernel(_pack(leaf, block_rows)[0]) for leaf in leaves)


def fused_scale_noise_tree(tree: Any, key, gain, noise_scale, *,
                           block_rows: int = 256,
                           interpret=False) -> Any:
    """leaf * gain + Laplace(noise_scale) in ONE fused HBM pass per leaf.

    `gain` and `noise_scale` may be traced scalars (e.g. a clip factor and
    an owner-indexed Theorem-1 scale). The Laplace bits come from
    jax.random (threefry), converted in-kernel by inverse CDF — note this
    is a DIFFERENT lawful draw than jax.random.laplace, so the jnp and
    fused backends are statistically, not bitwise, equivalent.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    if interpret == "oracle":
        from repro.kernels.dp_clip_noise.ref import scale_noise_ref
        out = [scale_noise_ref(leaf, jax.random.bits(k, leaf.shape, jnp.uint32),
                               gain, noise_scale)
               for leaf, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, out)
    packed = [_pack(leaf, block_rows) for leaf in leaves]
    cs = jnp.asarray(gain, jnp.float32).reshape(1, 1)
    ns = jnp.asarray(noise_scale, jnp.float32).reshape(1, 1)
    out = []
    for (p, n), leaf, k in zip(packed, leaves, keys):
        bits = jax.random.bits(k, p.shape, jnp.uint32)
        y = scale_noise_2d(p, bits, cs, ns, block_rows=block_rows,
                           interpret=interpret)
        out.append(y.reshape(-1)[:n].reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
