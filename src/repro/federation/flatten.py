"""Flat-parameter representation for the deep-path round engine.

The inertia round (eqs. 5-7) is elementwise in every parameter: averaging,
the regularizer gradient, both updates, and the Theta projection never mix
elements. Running it as ~7 `tree_map` passes over L leaves costs O(L) kernel
launches per pass and defeats fusion across leaves; the owner BANK pays a
per-leaf `dynamic_index/update` on top. Packing the model into ONE
contiguous f32 buffer turns the whole round into a handful of 1-D ops over
a single array, the bank into an `(N_owners, P)` matrix whose gather/
scatter is one row slice, and gives the Pallas `dp_round` kernel a layout
it can stream in a single HBM pass.

    spec = flatten_spec(params)         # static: treedef/shapes/dtypes
    flat = pack_params(params)          # ParamFlat: (P,) f32 + spec
    tree = flat.unpack()                # exact round-trip

`ParamFlat` is a registered pytree whose buffer is the only traced leaf and
whose `FlatSpec` rides as static aux data, so jitted functions specialize
per model structure exactly as they would on the pytree itself.

Exactness contract: the buffer is float32. Packing is bit-exact for every
floating dtype of itemsize <= 4 (f32 trivially; f16/bf16 embed exactly in
f32 and round-trip exactly back). Wider or non-floating leaves would make
the round-trip lossy, so they are rejected loudly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_PACKABLE = ("float32", "bfloat16", "float16")
_QUANT_FMTS = ("int8", "fp8")


def _check_dtype(dt: np.dtype) -> np.dtype:
    if dt.name not in _PACKABLE:
        raise TypeError(
            f"cannot pack dtype {dt.name!r} into the f32 flat buffer "
            f"without losing bits (packable: {', '.join(_PACKABLE)})")
    return dt


def _concat_f32(leaves) -> jax.Array:
    return jnp.concatenate([jnp.ravel(leaf).astype(jnp.float32)
                            for leaf in leaves])


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static shape/dtype/layout metadata of a packed pytree.

    Hashable (usable as static jit aux data); equality means two buffers
    describe the same model structure and may be exchanged.
    """
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]                # np.dtype per leaf
    offsets: Tuple[int, ...]               # start of each leaf in the buffer
    size: int                              # P = total elements

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    def validate(self, tree) -> list:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"tree structure mismatch: got {treedef}, "
                             f"spec has {self.treedef}")
        for leaf, shape, dt in zip(leaves, self.shapes, self.dtypes):
            if tuple(leaf.shape) != shape:
                raise ValueError(f"leaf shape mismatch: got {leaf.shape}, "
                                 f"spec has {shape}")
            if np.dtype(leaf.dtype) != dt:
                # silent astype through the f32 buffer could drop bits
                # (f64 under x64, ints); the contract is loud rejection
                raise TypeError(f"leaf dtype mismatch: got {leaf.dtype}, "
                                f"spec has {dt}")
        return leaves

    def pack(self, tree, sharding=None) -> jax.Array:
        """Pytree -> (P,) f32 buffer. Exact (see module docstring).

        `sharding` (a NamedSharding, e.g. `FlatShardings.theta` from
        repro.sharding.rules) lays the buffer out on the mesh: under a
        trace it becomes a `with_sharding_constraint` (so packing inside
        a jitted round keeps the buffer sharded instead of gathering it),
        eagerly it reshards the concrete array. Values are identical
        either way."""
        leaves = self.validate(tree)
        buf = _concat_f32(leaves)
        if sharding is not None:
            buf = jax.lax.with_sharding_constraint(buf, sharding)
        return buf

    def _leaf_slices(self, buf: jax.Array) -> list:
        """The (n,) slice of every leaf, behind an optimization barrier.

        Without the barrier XLA rewrites slice-then-reshape of the whole
        buffer into one reshape of the buffer to (P/k, k), k the smallest
        minor dimension among the leaves, followed by row slices. On TPU
        a minor dimension of k < 128 pads every tile to 128 lanes: for
        xlstm-125m (k = 4) that is 12 GB of the 16 GB chip. The barrier
        costs one copy of the buffer."""
        if buf.shape != (self.size,):
            raise ValueError(f"buffer shape {buf.shape} != ({self.size},)")
        return jax.lax.optimization_barrier([
            buf[off:off + (int(np.prod(shape, dtype=np.int64))
                           if shape else 1)]
            for off, shape in zip(self.offsets, self.shapes)])

    def unpack(self, buf: jax.Array) -> Any:
        """(P,) buffer -> pytree with the original shapes/dtypes."""
        leaves = [part.reshape(shape).astype(dt) for part, shape, dt in
                  zip(self._leaf_slices(buf), self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # Shape-only variants for SIDE-CHANNEL buffers that ride the model's
    # layout but must stay f32 regardless of leaf dtype — e.g. the tree
    # mechanism's noise rows and retired-node corrections: casting those
    # through a bf16 model's leaf dtypes would corrupt the noise the DP
    # guarantee is calibrated to.

    def pack_f32(self, tree) -> jax.Array:
        """Pytree with the spec's SHAPES (any floating dtype) -> (P,) f32
        buffer; shapes are validated, leaf dtypes are NOT."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"tree structure mismatch: got {treedef}, "
                             f"spec has {self.treedef}")
        for shape, leaf in zip(self.shapes, leaves):
            if tuple(leaf.shape) != shape:
                raise ValueError(f"leaf shape mismatch: got {leaf.shape}, "
                                 f"spec has {shape}")
        return _concat_f32(leaves)

    def unpack_f32(self, buf: jax.Array) -> Any:
        """(P,) f32 buffer -> pytree with the spec's shapes, dtype KEPT
        f32 (no per-leaf downcast)."""
        leaves = [part.reshape(shape) for part, shape in
                  zip(self._leaf_slices(buf), self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def flatten_spec(tree) -> FlatSpec:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot flatten a pytree with no array leaves")
    shapes, dtypes, offsets = [], [], []
    off = 0
    for leaf in leaves:
        shapes.append(tuple(leaf.shape))
        dtypes.append(_check_dtype(np.dtype(leaf.dtype)))
        offsets.append(off)
        off += int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
    return FlatSpec(treedef=treedef, shapes=tuple(shapes),
                    dtypes=tuple(dtypes), offsets=tuple(offsets), size=off)


@jax.tree_util.register_pytree_node_class
class ParamFlat:
    """One contiguous f32 master copy of a model pytree.

    Traced leaf: `buf` (P,) f32. Static aux: `spec` (FlatSpec). Elementwise
    updates on `buf` are bit-identical to the same per-leaf updates on the
    f32 pytree, which is what makes the flat round engine's
    `fused_kernel=False` mode exactly reproduce the tree path.
    """

    def __init__(self, buf: jax.Array, spec: FlatSpec):
        self.buf = buf
        self.spec = spec

    def tree_flatten(self):
        return (self.buf,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)

    @property
    def size(self) -> int:
        return self.spec.size

    def unpack(self) -> Any:
        return self.spec.unpack(self.buf)

    def replace_buf(self, buf: jax.Array) -> "ParamFlat":
        return ParamFlat(buf, self.spec)

    def __repr__(self) -> str:
        return (f"ParamFlat(P={self.spec.size}, "
                f"n_leaves={self.spec.n_leaves})")


def pack_params(tree, spec: FlatSpec = None, sharding=None) -> ParamFlat:
    """Pack a model pytree into a ParamFlat (spec inferred if omitted).
    `sharding` lays the buffer out on a mesh (see FlatSpec.pack)."""
    spec = flatten_spec(tree) if spec is None else spec
    return ParamFlat(spec.pack(tree, sharding=sharding), spec)


@dataclasses.dataclass(frozen=True)
class BankCodec:
    """Static configuration of a quantized owner bank (hashable: rides as
    pytree aux data, so jitted round functions specialize per codec).

    fmt          — "int8" (symmetric linear code, q in [-127, 127]) or
                   "fp8" (float8_e4m3fn grid). 1 byte/element either way.
    block_elems  — None: one f32 scale per bank row (the default, and the
                   only layout the Pallas kernel path supports). An int
                   switches to per-block scales: each row is cut into
                   ceil(P/block_elems) segments with their own absmax
                   scale (oracle backend only; finer dynamic range for
                   rows that mix layer magnitudes).
    """
    fmt: str
    block_elems: Optional[int] = None

    def __post_init__(self):
        if self.fmt not in _QUANT_FMTS:
            raise ValueError(f"unknown bank codec {self.fmt!r} "
                             f"(supported: {', '.join(_QUANT_FMTS)})")
        if self.block_elems is not None and self.block_elems < 1:
            raise ValueError(f"block_elems must be >= 1, "
                             f"got {self.block_elems}")

    @property
    def code_dtype(self):
        from repro.kernels.bank_codec.ops import code_dtype
        return code_dtype(self.fmt)

    def n_scales(self, p: int) -> int:
        from repro.kernels.bank_codec.ops import n_scales
        return n_scales(p, self.block_elems)


def as_bank_codec(dtype) -> Optional[BankCodec]:
    """Normalize a `bank_dtype` option: "int8"/"fp8" (or a BankCodec) mean
    the quantized bank; None or a real floating dtype mean the dense
    storage path (returns None). Unknown strings fail loudly."""
    if isinstance(dtype, BankCodec):
        return dtype
    if isinstance(dtype, str):
        if dtype in _QUANT_FMTS:
            return BankCodec(dtype)
        if dtype not in _PACKABLE:           # "bfloat16" etc: dense path
            raise ValueError(
                f"unknown bank_dtype {dtype!r}: expected a floating dtype "
                f"({', '.join(_PACKABLE)}) or a quantized format "
                f"({', '.join(_QUANT_FMTS)})")
    return None


@jax.tree_util.register_pytree_node_class
class QuantBank:
    """Quantized owner bank: `(N_owners, P)` 1-byte codes, `(N_owners, nb)`
    f32 scales, and ONE shared `(P,)` f32 error-feedback residual row.

    The residual holds the quantization error of the LAST granted scatter
    (err = value - decode(encode(value))); the round engine adds it to the
    next granted update before encoding, so quantization error is
    re-injected into training instead of lost — the total error in flight
    is always one row's worth, never accumulating. A refused round leaves
    codes, scales AND residual untouched (refusal stays a bit-exact no-op
    on the bank).

    Resident bytes: N*P (codes) + 4*N*nb (scales) + 4*P (residual) —
    ~N*P/(4*N*P) = 4x below the f32 bank as N grows (3.6x at N=32).
    Traced leaves: codes/scales/residual; the BankCodec is static aux.
    """

    def __init__(self, codes: jax.Array, scales: jax.Array,
                 residual: jax.Array, codec: BankCodec):
        self.codes = codes
        self.scales = scales
        self.residual = residual
        self.codec = codec

    def tree_flatten(self):
        return (self.codes, self.scales, self.residual), self.codec

    @classmethod
    def tree_unflatten(cls, codec, children):
        return cls(*children, codec)

    @property
    def n_owners(self) -> int:
        return self.codes.shape[0]

    @property
    def size(self) -> int:
        return self.codes.shape[-1]

    @property
    def nbytes(self) -> int:
        return self.codes.nbytes + self.scales.nbytes + self.residual.nbytes

    def decode_rows(self, interpret="oracle") -> jax.Array:
        """(N, P) f32 view of every owner copy (tests/inspection)."""
        from repro.kernels.bank_codec.ops import decode_row
        return jax.vmap(lambda c, s: decode_row(
            c, s, self.codec.fmt, block_elems=self.codec.block_elems,
            interpret=interpret))(self.codes, self.scales)

    def replace(self, **kw) -> "QuantBank":
        args = {"codes": self.codes, "scales": self.scales,
                "residual": self.residual, "codec": self.codec}
        args.update(kw)
        return QuantBank(**args)

    def __repr__(self) -> str:
        return (f"QuantBank(fmt={self.codec.fmt!r}, "
                f"N={self.n_owners}, P={self.size})")


@jax.tree_util.register_pytree_node_class
class PagedBank:
    """Paged owner bank: a device-resident working set of `n_hot` rows
    backed by a host cold tier (see ``repro.federation.paging``).

    `hot` is the resident tier — a dense `(n_hot, P)` matrix or a
    `QuantBank` with `n_hot` rows (codes + scales + the shared EF
    residual, which belongs to the *session*, not to any owner, and
    therefore never pages). `hot_ids` is the device-resident page table:
    a SORTED `(n_hot,)` int32 vector of the owner ids resident in each
    slot, with the sentinel `n_owners` marking empty slots (the sentinel
    sorts after every real id, so the vector stays sorted by
    construction). `n_owners` (static aux) is the federation size N —
    resident bytes are O(n_hot * row), independent of N.

    `lookup` resolves owner id -> hot slot IN-GRAPH via
    ``jnp.searchsorted`` over the sorted page table — no host sync
    inside a scan body — and returns a `hit` bit the drivers fold into
    their grant mask, so a round touching a non-resident owner is a
    bit-exact masked no-op (the clamped slot's row is written back to
    itself), exactly like a ledger refusal. The host-side
    ``paging.OwnerPager`` keeps the working set ahead of the schedule so
    misses never occur in a correctly-driven session.
    """

    def __init__(self, hot, hot_ids: jax.Array, n_owners: int):
        self.hot = hot
        self.hot_ids = hot_ids
        self.n_owners = n_owners

    def tree_flatten(self):
        return (self.hot, self.hot_ids), self.n_owners

    @classmethod
    def tree_unflatten(cls, n_owners, children):
        return cls(children[0], children[1], n_owners)

    @property
    def n_hot(self) -> int:
        return self.hot_ids.shape[0]

    @property
    def size(self) -> int:
        return self.hot.size if isinstance(self.hot, QuantBank) \
            else self.hot.shape[-1]

    @property
    def codec(self) -> Optional[BankCodec]:
        return self.hot.codec if isinstance(self.hot, QuantBank) else None

    @property
    def nbytes(self) -> int:
        """Device-resident bytes: the hot tier + the page table."""
        hot = (self.hot.nbytes if isinstance(self.hot, QuantBank)
               else self.hot.nbytes)
        return hot + self.hot_ids.nbytes

    def lookup(self, owner_idx) -> Tuple[jax.Array, jax.Array]:
        """owner id -> (slot, hit), both traced; vmap-safe.

        `slot` is clamped into [0, n_hot) so it is ALWAYS a safe gather
        index; `hit` is False when the owner is not resident (the
        clamped slot then points at some other owner's row, which the
        drivers' masked writes leave bit-exactly untouched)."""
        slot = jnp.searchsorted(self.hot_ids,
                                jnp.asarray(owner_idx, jnp.int32))
        slot = jnp.minimum(slot, self.n_hot - 1).astype(jnp.int32)
        hit = self.hot_ids[slot] == owner_idx
        return slot, hit

    def replace(self, **kw) -> "PagedBank":
        args = {"hot": self.hot, "hot_ids": self.hot_ids,
                "n_owners": self.n_owners}
        args.update(kw)
        return PagedBank(**args)

    def __repr__(self) -> str:
        fmt = self.codec.fmt if self.codec is not None else str(
            self.hot.dtype)
        return (f"PagedBank(n_hot={self.n_hot}, N={self.n_owners}, "
                f"P={self.size}, storage={fmt!r})")


def init_flat_bank(flat: ParamFlat, n_owners: int, dtype=None,
                   sharding=None, scales_sharding=None,
                   residual_sharding=None):
    """(N_owners, P) owner-copy bank, every row the central buffer.

    `dtype` (default float32) is the bank STORAGE dtype. The bank is the
    algorithm's dominant memory cost (N_owners copies of the model) and,
    in the fused multi-round scan, its dominant loop-carry traffic;
    bf16 storage halves both. The strings "int8"/"fp8" (or a `BankCodec`)
    select the QUANTIZED bank instead: 1-byte codes + per-row f32 scales
    + an error-feedback residual row (~4x below f32, see `QuantBank`).
    The initial encode is the deterministic round-to-nearest (keyless,
    reproducible); its one-time O(scale) error is identical across rows
    and the residual starts at zero. Dense rows upcast to f32 on gather
    and re-quantize on scatter (a refused round's untouched row
    round-trips exactly). Only f32 storage preserves the flat-vs-tree
    bit-parity contract — narrower banks are a recorded (opt-in)
    deviation.

    `sharding` (e.g. `FlatShardings.bank`: owner rows over the data axes,
    P like the model) materializes the bank already distributed — the
    broadcast never exists replicated on one device. Quantized banks
    take `scales_sharding`/`residual_sharding` for their extra buffers
    (`FlatShardings.bank_scales` / `.row`).
    """
    codec = as_bank_codec(dtype)
    if codec is not None:
        from repro.federation.dp_sgd import resolve_interpret
        from repro.kernels.bank_codec.ops import encode_row
        codes_row, scales_row, _ = encode_row(
            flat.buf, None, codec.fmt, block_elems=codec.block_elems,
            deterministic=True, interpret=resolve_interpret(None))
        codes = jnp.broadcast_to(codes_row[None], (n_owners, flat.size))
        scales = jnp.broadcast_to(scales_row[None],
                                  (n_owners, scales_row.shape[0]))
        residual = jnp.zeros((flat.size,), jnp.float32)
        if sharding is not None:
            codes = jax.lax.with_sharding_constraint(codes, sharding)
        if scales_sharding is not None:
            scales = jax.lax.with_sharding_constraint(scales,
                                                      scales_sharding)
        if residual_sharding is not None:
            residual = jax.lax.with_sharding_constraint(residual,
                                                        residual_sharding)
        return QuantBank(codes, scales, residual, codec)
    # narrow before broadcasting: an eager f32 (N, P) intermediate would
    # cost twice the bf16 bank it becomes
    row = flat.buf if dtype is None else flat.buf.astype(dtype)
    bank = jnp.broadcast_to(row[None], (n_owners, flat.size))
    if sharding is not None:
        bank = jax.lax.with_sharding_constraint(bank, sharding)
    return bank
