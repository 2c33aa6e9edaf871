"""Scalable gradient privatization: Xi-enforcement for non-convex models.

Canonical home; ``repro.core.dp_sgd`` is a compatibility shim over this
module.

Assumption 2 (bounded per-record gradient) does not hold for transformers;
we enforce it by clipping before averaging — the standard DP-SGD adaptation.
Granularities:

  'example'    — per-example grads via vmap(grad), clip each to xi, average.
                 Exact Assumption-2 enforcement; memory O(batch * params):
                 use for small models / smoke tests.
  'microbatch' — lax.scan over microbatch groups; each *group* gradient is
                 clipped to xi and groups are averaged. Memory O(params);
                 required at 100B scale. DP adjacency unit becomes a GROUP
                 (group-level DP) — the accountant records n = n_groups.

The fused clip+noise hot-path has a Pallas kernel
(`repro.kernels.dp_clip_noise`) — a single HBM pass instead of three.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.federation.privacy import laplace_noise_tree

LossFn = Callable[[Any, Dict[str, jax.Array]], jax.Array]


@dataclasses.dataclass(frozen=True)
class PrivatizerConfig:
    xi: float                       # clip norm (== Assumption-2 bound)
    granularity: str = "microbatch"  # 'example' | 'microbatch'
    n_microbatches: int = 8
    mechanism: str = "laplace"      # 'laplace' | 'gaussian' (beyond-paper)
    # pre_grouped: batch leaves arrive (G, B/G, ...) microbatch-major.
    # §Perf iteration 11: the in-graph (B,)->(G,B/G) reshape of a
    # batch-sharded tensor defeats GSPMD on the multi-pod mesh
    # ("involuntary full rematerialization" -> pod axis replicated, train
    # steps get NO multi-pod speedup). Grouping at the input layout fixes it.
    pre_grouped: bool = False
    # fused_kernel: route the clip-norm reduction and the final
    # mean+Laplace-add through the Pallas dp_clip_noise kernels (one HBM
    # pass instead of three), traced-scalar-safe so it fuses into the
    # multi-round scan body. The in-kernel inverse-CDF Laplace draw is a
    # different lawful sample than jax.random.laplace, so this backend is
    # statistically (not bitwise) equivalent to the jnp one. laplace only.
    fused_kernel: bool = False
    kernel_block_rows: int = 256
    # None = auto-detect the kernel backend: compiled Pallas on TPU, the
    # kernel's jnp oracle transform elsewhere (same math, no emulation
    # plumbing). True forces the Pallas interpreter (kernel debugging);
    # False forces the compiled kernel.
    kernel_interpret: Optional[bool] = None


def resolve_interpret(flag: Optional[bool]):
    """Kernel-backend auto-detection for the `interpret` argument of the
    dp_clip_noise ops: explicit True/False forces the Pallas interpreter /
    compiled kernel; None picks per backend — compiled on TPU (no manual
    config needed), the op's jnp "oracle" transform elsewhere (the Pallas
    interpreter is a debugging device, not an execution backend)."""
    if flag is None:
        return False if jax.default_backend() == "tpu" else "oracle"
    return bool(flag)


def _global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                        for leaf in leaves))


def clip_tree(tree, max_norm: float):
    norm = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda leaf: (leaf.astype(jnp.float32) * scale).astype(leaf.dtype), tree), norm


def _group_batch(batch, n_groups):
    """Reshape every leaf (B, ...) -> (G, B/G, ...) for scan-over-groups."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups, a.shape[0] // n_groups) + a.shape[1:]),
        batch)


def private_grad(loss_fn: LossFn, params, batch, key, *,
                 cfg: PrivatizerConfig, noise_scale: float,
                 return_noise: bool = False
                 ) -> Tuple[Any, ...]:
    """Clipped-average gradient + mechanism noise (the DP response, eq. 4).

    noise_scale is the Theorem-1 scale for the *averaged* query; returns
    (noisy grad pytree, metrics).

    `return_noise=True` appends the drawn noise pytree as a THIRD return
    value — (noisy, metrics, noise) — without changing the draw or the
    noisy sum in any way. The tree mechanism needs the fresh draw
    separately (it becomes the tree's fresh node while retired nodes are
    subtracted from the response), and re-drawing it outside would
    double-consume the round key; jnp laplace/gaussian only — the fused
    kernel adds its noise in-kernel and never materializes it.
    """
    if return_noise and cfg.fused_kernel:
        raise ValueError("return_noise requires the jnp mechanism path "
                         "(fused_kernel adds noise in-kernel)")
    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if cfg.pre_grouped and cfg.granularity == "microbatch":
        B = cfg.n_microbatches * jax.tree_util.tree_leaves(batch)[0].shape[1]

    # named scopes split the response into the gradient, the clipping
    # and the noise, for the device profile
    if cfg.granularity == "example":
        def one(ex):
            ex1 = jax.tree_util.tree_map(lambda a: a[None], ex)
            with jax.named_scope("fed.grad"):
                return jax.grad(lambda p: loss_fn(p, ex1))(params)
        grads = jax.vmap(one)(batch)                 # leaves (B, ...)
        with jax.named_scope("fed.clip"):
            norms = jax.vmap(lambda i: _global_norm(
                jax.tree_util.tree_map(lambda leaf: leaf[i], grads)))(
                    jnp.arange(B))
            scale = jnp.minimum(1.0, cfg.xi / jnp.maximum(norms, 1e-12))
            mean_grad = jax.tree_util.tree_map(
                lambda leaf: jnp.mean(
                    leaf.astype(jnp.float32)
                    * scale.reshape((-1,) + (1,) * (leaf.ndim - 1)),
                    axis=0), grads)
            clip_frac = jnp.mean((norms > cfg.xi).astype(jnp.float32))
            max_norm = jnp.max(norms)
    elif cfg.granularity == "microbatch":
        G = cfg.n_microbatches
        assert B % G == 0, (B, G)

        def body(carry, mb):
            acc, nclip, mx = carry
            with jax.named_scope("fed.grad"):
                g = jax.grad(lambda p: loss_fn(p, mb))(params)
            with jax.named_scope("fed.clip"):
                if cfg.fused_kernel:
                    from repro.kernels.dp_clip_noise.ops import \
                        fused_sqnorm_tree
                    norm = jnp.sqrt(fused_sqnorm_tree(
                        g, block_rows=cfg.kernel_block_rows,
                        interpret=resolve_interpret(cfg.kernel_interpret)))
                    s = jnp.minimum(1.0, cfg.xi / jnp.maximum(norm, 1e-12))
                    g = jax.tree_util.tree_map(
                        lambda leaf: (leaf.astype(jnp.float32) * s
                                      ).astype(leaf.dtype), g)
                else:
                    g, norm = clip_tree(g, cfg.xi)
                acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return (acc, nclip + (norm > cfg.xi),
                        jnp.maximum(mx, norm)), None

        with jax.named_scope("fed.clip"):
            zeros = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(leaf.shape, jnp.float32), params)
        xs = batch if cfg.pre_grouped else _group_batch(batch, G)
        (acc, nclip, max_norm), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)), xs)
        with jax.named_scope("fed.clip"):
            mean_grad = jax.tree_util.tree_map(lambda a: a / G, acc)
            clip_frac = nclip / G
    else:
        raise ValueError(cfg.granularity)

    if cfg.fused_kernel:
        if cfg.mechanism != "laplace":
            raise ValueError("fused_kernel implements the laplace mechanism")
        from repro.kernels.dp_clip_noise.ops import fused_scale_noise_tree
        # One pass: the group-mean divide (gain=1/G) and the Laplace add
        # fuse with the write-out; for 'example' the mean is already taken.
        src, gain = ((acc, 1.0 / G) if cfg.granularity == "microbatch"
                     else (mean_grad, 1.0))
        with jax.named_scope("fed.noise"):
            noisy = fused_scale_noise_tree(
                src, key, gain, noise_scale,
                block_rows=cfg.kernel_block_rows,
                interpret=resolve_interpret(cfg.kernel_interpret))
        return noisy, {"clip_frac": clip_frac, "max_grad_norm": max_norm}

    with jax.named_scope("fed.noise"):
        if cfg.mechanism == "laplace":
            noise = laplace_noise_tree(key, mean_grad, noise_scale)
        elif cfg.mechanism == "gaussian":
            leaves, treedef = jax.tree_util.tree_flatten(mean_grad)
            ks = jax.random.split(key, len(leaves))
            noise = jax.tree_util.tree_unflatten(
                treedef, [noise_scale
                          * jax.random.normal(k, leaf.shape, jnp.float32)
                          for k, leaf in zip(ks, leaves)])
        else:
            raise ValueError(cfg.mechanism)
        noisy = jax.tree_util.tree_map(lambda g, w: g + w, mean_grad, noise)
    metrics = {"clip_frac": clip_frac, "max_grad_norm": max_norm}
    if return_noise:
        return noisy, metrics, noise
    return noisy, metrics
