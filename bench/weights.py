"""Seeded random weights, made by the benchmark in one jitted call.

The program gives only the layout (`jax.eval_shape` of its init); every
value is drawn here, from the run's seed, by the leaf's name: gains of
norms are ones, gate biases open, state-space decay rates spread over
their published ranges, and matrices are truncated normals scaled by
their fan-in. The same weights go to the program and to the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ONES = {"ln_f", "ln", "ln1", "ln2", "ln3", "shared_ln", "norm", "D"}
FIXED_SCALE = {"embed": 0.02, "unembed": 0.02, "conv": 0.5}


def leaf_name(path) -> str:
    last = path[-1]
    for attr in ("key", "name"):
        if hasattr(last, attr):
            return str(getattr(last, attr))
    return str(last)


def stacked(path) -> bool:
    """Leaves under a dict of stacked layers carry a leading layer axis."""
    return (len(path) > 1 and getattr(path[0], "key", None) == "blocks"
            and not hasattr(path[1], "idx"))


def _leaf(key, path, sds):
    name, shape = leaf_name(path), tuple(sds.shape)
    n = shape[-1]
    if name in ONES:
        v = jnp.ones(shape, jnp.float32)
    elif name == "b_f":                              # mLSTM forget bias
        v = jnp.full(shape, 3.0, jnp.float32)
    elif name == "b":                                # sLSTM gate biases
        v = jnp.zeros(shape, jnp.float32).at[..., 1].set(3.0)
    elif name == "A_log":
        v = jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, n)), shape)
    elif name == "dt_bias":                          # softplus^-1 of dt
        v = jnp.broadcast_to(jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, n))),
                             shape)
    else:
        per_layer = shape[1:] if stacked(path) else shape
        fan_in = int(np.prod(per_layer[:-1])) if len(per_layer) > 1 else n
        scale = FIXED_SCALE.get(name, 1.0 / max(fan_in, 1) ** 0.5)
        v = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)
    return v.astype(sds.dtype)


def weight_fn(shapes):
    """Jitted key -> weights with the layout of `shapes` (a pytree of
    ShapeDtypeStruct); the key is a raw (2,) uint32 array."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), path, sds)
                  for i, (path, sds) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)


def weight_key(seed: int) -> jax.Array:
    return jnp.asarray(np.random.SeedSequence([seed, 0]).generate_state(
        2, np.uint32))
