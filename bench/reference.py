"""Plain reference of one private federated round, in jax.numpy.

It imports nothing of the program. It follows the rounds of the first
dispatch from the same weights, records, owner sequence and keys, and
gives what the program's state and round metrics should hold:

- the model's loss gradient for each record, clipped to the clip norm
  (per-record clipping: one sequence per microbatch), from the
  configuration's own plain loss (`configs/<config>.py`, `lm_loss`);
- the Laplace draw of Theorem 1 (scale 2 * clip * T / (n_i * eps_i)):
  threefry bits of the round key over the flat parameter vector, which
  under JAX's partitionable threefry are the first P bits of the padded
  (rows, 1024) blocks the program draws over, the top 24 bits as a
  uniform, and the inverse CDF;
- the inertia update of eqs. 5-7 with the paper's rates, the theta_max
  projection, and the owner's row written to a bf16 bank.

Matrix products run at `highest` precision in float32 (a TPU otherwise
multiplies float32 in bf16 passes). `dtype=bfloat16` gives the control:
the same reference one precision lower.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------- flat parameters
@dataclasses.dataclass(frozen=True)
class Layout:
    """Leaves of the model in flattening order, laid end to end."""
    treedef: Any
    shapes: List[tuple]
    offsets: List[int]
    size: int

    @classmethod
    def of(cls, shapes_tree) -> "Layout":
        leaves, treedef = jax.tree_util.tree_flatten(shapes_tree)
        shapes = [tuple(leaf.shape) for leaf in leaves]
        sizes = [int(np.prod(s)) for s in shapes]
        return cls(treedef, shapes, list(np.cumsum([0] + sizes[:-1])),
                   int(sum(sizes)))

    def unflat(self, buf):
        """Leaves of a flat buffer. The slices sit behind an optimisation
        barrier: without it XLA reshapes the whole buffer to (P/k, k), k
        the smallest minor dimension of any leaf, which a TPU pads to 128
        lanes (25 GB for xlstm-125m)."""
        parts = jax.lax.optimization_barrier(
            [buf[o:o + int(np.prod(s))] for o, s in
             zip(self.offsets, self.shapes)])
        leaves = [p.reshape(s) for p, s in zip(parts, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def flat(self, tree):
        return jnp.concatenate([leaf.reshape(-1) for leaf in
                                jax.tree_util.tree_leaves(tree)])

    def leaf_norms(self, buf, ref=None):
        """(n_leaves,) L2 norm of each leaf of a flat buffer, or of its
        difference from the leaves of `ref` (a tree of this layout)."""
        refs = (jax.tree_util.tree_leaves(ref) if ref is not None
                else [None] * len(self.shapes))
        out = []
        for o, s, r in zip(self.offsets, self.shapes, refs):
            part = buf[o:o + int(np.prod(s))].astype(jnp.float32)
            if r is not None:
                part = part - r.reshape(-1).astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(part))))
        return jnp.stack(out)


def laplace(key, layout: Layout, dtype):
    """Unit Laplace draws over the flat vector: threefry bits of `key`
    over (P,), top 24 bits -> u in [0, 1), inverse CDF. The grid's end
    point u = 0 is clamped to the nearest interior value.

    Under JAX's partitionable threefry (its default) an element's bits
    depend only on its flat index, so these are the first P bits of the
    padded (rows, 1024) blocks. XLA:TPU fuses the draw over (P,) into its
    consumers; a (rows, 1024) draw materialises its counters, 10.0 GB of
    temporaries at P = 501,156,000."""
    if not jax.config.jax_threefry_partitionable:
        raise ValueError("the reference's noise needs "
                         "jax_threefry_partitionable: without it the bits "
                         "over (P,) are not those of the padded blocks")
    bits = jax.random.bits(key, (layout.size,), jnp.uint32)
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * 2.0 ** -24
    v = jnp.clip(u - 0.5, -0.4999999, 0.4999999)
    return (-jnp.sign(v) * jnp.log1p(-2.0 * jnp.abs(v))).astype(dtype)


# -------------------------------------------------------------- the round
@dataclasses.dataclass(frozen=True)
class Round:
    """The constants of a round, from the cell's configuration and
    traffic (paper's rates with the owner rate pinned to target_lr)."""
    n_owners: int
    horizon: int
    clip: float
    sigma: float
    theta_max: float
    target_lr: float
    records: Sequence[int]
    epsilons: Sequence[float]

    @property
    def lr_own(self) -> float:
        return self.target_lr

    @property
    def lr_L(self) -> float:
        N = self.n_owners
        return self.target_lr * (N - 1) / N ** 2

    def noise_scale(self, i: int) -> float:
        return 2.0 * self.clip * self.horizon / (self.records[i]
                                                 * self.epsilons[i])

    def weight(self, i: int) -> float:
        return self.records[i] / float(sum(self.records))


class Reference:
    def __init__(self, model: Dict[str, Any], loss: Callable, shapes_tree,
                 rnd: Round, *, dtype=jnp.float32, half_batch: bool = False):
        self.m = model
        self.layout = Layout.of(shapes_tree)
        self.rnd = rnd
        self.dtype = jnp.dtype(dtype)
        self.half_batch = half_batch
        L = self.layout
        dt = self.dtype

        def round_grads(tb, tokens, labels):
            """Clipped gradient sum over a round's records (B, S), and the
            largest per-record norm. The records run one at a time, so
            one record's gradient and activations are live at once, not
            B of them."""
            params = L.unflat(tb.astype(dt))

            def one(carry, record):
                acc, top = carry
                t, lab = record
                g = L.flat(jax.grad(loss)(params, t, lab, model)).astype(dt)
                norm = jnp.sqrt(jnp.sum(g * g))
                s = jnp.minimum(1.0, rnd.clip / jnp.maximum(norm, 1e-12))
                acc = acc + (g * s.astype(dt)).astype(jnp.float32)
                return (acc, jnp.maximum(top, norm)), None

            init = (jnp.zeros(L.size, jnp.float32), jnp.zeros((), dt))
            (acc, top), _ = jax.lax.scan(one, init, (tokens, labels))
            return acc, top

        def update(tb, acc, key, gain, b, w):
            tb = tb.astype(dt)
            q = acc.astype(dt) * gain + b * laplace(key, L, dt)
            g_reg = rnd.sigma * tb
            new_i = jnp.clip(tb - rnd.lr_own * (g_reg / (2 * rnd.n_owners)
                                                + w * q),
                             -rnd.theta_max, rnd.theta_max)
            new_L = jnp.clip(tb - rnd.lr_L * g_reg, -rnd.theta_max,
                             rnd.theta_max)
            return new_L.astype(jnp.float32), new_i.astype(jnp.bfloat16)

        self._grads = jax.jit(round_grads)
        self._update = jax.jit(update, donate_argnums=(0, 1))
        self._tb = jax.jit(lambda a, r: (0.5 * (a.astype(dt)
                                                + r.astype(dt))
                                         ).astype(jnp.float32))

    def _precision(self):
        if self.dtype == jnp.float32:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    def follow(self, theta0, owner_seq: np.ndarray, batches: Dict,
               key, rounds: int) -> Dict[str, Any]:
        """Run the first rounds of a dispatch of `rounds` rounds (whose
        round keys are split from `key`) from weights `theta0` (flat f32):
        one round per entry of `owner_seq`. Returns the largest
        per-record gradient norm of each round, the learner's model and
        the bank rows the rounds wrote. Where the caller keeps no other
        reference to `theta0`, it is freed once the first round has read
        it."""
        rnd = self.rnd
        theta_L = theta0
        row0 = theta0.astype(jnp.bfloat16)
        del theta0
        rows: Dict[int, Any] = {}
        keys = jax.random.split(jnp.asarray(key, jnp.uint32), rounds)
        B = batches["tokens"].shape[1]
        used = B // 2 if self.half_batch else B
        max_norm = []
        with self._precision():
            for k, o in enumerate(np.asarray(owner_seq)):
                o = int(o)
                row = rows.get(o, row0)
                tb = self._tb(theta_L, row)
                del theta_L
                acc, n = self._grads(tb,
                                     jnp.asarray(batches["tokens"][k, :used]),
                                     jnp.asarray(batches["labels"][k, :used]))
                max_norm.append(float(n))
                theta_L, rows[o] = self._update(
                    tb, acc, keys[k], 1.0 / used, rnd.noise_scale(o),
                    rnd.weight(o))
                del tb, acc
        return {"max_grad_norm": np.asarray(max_norm), "theta_L": theta_L,
                "rows": rows, "row0": row0}


def ledger(owner_seqs: Sequence[np.ndarray], n_owners: int, cap: int):
    """Granted and refused rounds per owner: an owner is served until it
    has answered `cap` rounds (Theorem 1's horizon), then refused."""
    granted = np.zeros(n_owners, np.int64)
    refused = np.zeros(n_owners, np.int64)
    for seq in owner_seqs:
        for o in np.asarray(seq):
            if granted[o] < cap:
                granted[o] += 1
            else:
                refused[o] += 1
    return granted, refused
