"""Deep-model federation engine: Algorithm 1 as a first-class
distributed-training strategy for pytree models.

State = central params theta_L + an owner-copy BANK: every parameter leaf
gains a leading axis N_owners, sharded with the same FSDP x TP rules as the
model itself (see DESIGN.md §3 — one copy costs P/(|data|*|model|) bytes per
chip). A training step consumes `owner_idx` (drawn host-side from the
schedule), gathers that owner's copy, performs the paper's inertia update
(eqs. 5-7) with a privatized gradient (Theorem-1 Laplace scale, Xi enforced
by clipping per federation.dp_sgd), and writes the copy back.

The step intentionally contains NO cross-owner collective — that is the
paper's asynchrony, mapped to SPMD (the only collectives are model/data-axis
ones from sharding).

`lr_scale` (default 1.0) multiplies the paper's rho/T^2 constant rate —
the paper's exact rate is extremely small for deep nets; the override is a
recorded deviation for the practical examples, while paper-faithful runs
keep lr_scale=1.

Canonical home of the deep path; ``repro.core.async_trainer`` is a
compatibility shim over this module. The session-level entrypoint is
``repro.federation.Federation``: it injects per-owner noise `scales` from a
pluggable ``Mechanism`` (whose internal ledger refuses budget-exhausted
owners before the step is ever called).

Three drivers share the exact same round math (`_round_math`):

  make_train_step   — one host-authorized round per dispatch (the
                      mechanism's Python ledger decides refusal).
  make_fused_rounds — K rounds per dispatch via lax.scan, with budget
                      accounting device-resident (`AsyncDPState.ledger`, a
                      privacy.DeviceLedger): authorization is an in-graph
                      predicate and refusal is jnp.where masking, so
                      thousands of asynchronous rounds run without a host
                      round-trip. Bit-for-bit equal to the per-round loop
                      under the same per-round keys.
  make_group_rounds — owner-parallel mode: lax.scan over CONFLICT-FREE
                      round groups (consecutive rounds with distinct
                      owners, see schedules.partition_conflict_free), vmap
                      over the members of each group, ONE inertia
                      reduction of theta_L per group. Ledger spend is
                      exactly the sequential scan's; theta_L takes the
                      mean of the group's eq.(7) targets (a documented,
                      bounded deviation that vanishes at group size 1).

Every driver accepts `mesh=None`: given a Mesh, flat states are pinned to
the `repro.sharding.rules.flat_shardings` layout (bank rows over the data
axes, P like the model) with `jax.lax.with_sharding_constraint` INSIDE the
scan bodies, so the bank row gather/scatter stays local in P and the scan
carry never gathers to one device.

DP-FTRL tree noise (cfg.tree_depth, `TreeNoise` on the state): every
driver advances the per-owner binary noise tree INSIDE its scan body —
one row gather, a popcount-pattern node refresh (Pallas kernel family
`repro.kernels.tree_noise` on the fused flat path, its jnp oracle
elsewhere), one row scatter — with refusals masked to bit-exact no-ops
exactly like the bank, and tree nodes sharded like bank rows.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.federation import faults as _faults
from repro.federation.config import paper_rates
from repro.federation.dp_sgd import (PrivatizerConfig, _group_batch,
                                     private_grad, resolve_interpret)
from repro.federation.faults import FaultPolicy, FaultState, init_fault_state
from repro.federation.flatten import (FlatSpec, PagedBank, ParamFlat,
                                      QuantBank, init_flat_bank, pack_params)
from repro.federation.privacy import DeviceLedger, make_device_ledger
from repro.federation.staleness import (StalenessPolicy, StalenessState,
                                        deadline_guard, init_staleness_state,
                                        staleness_tick, staleness_weight)


@dataclasses.dataclass(frozen=True)
class AsyncDPConfig:
    n_owners: int
    horizon: int                       # T
    rho: float = 1.0
    sigma: float = 1e-4                # strong-convexity of g = (sigma/2)||.||^2
    epsilons: Sequence[float] = ()     # per-owner budgets
    owner_sizes: Sequence[int] = ()    # n_i (records per owner)
    xi: float = 1.0                    # clip norm / Assumption-2 bound
    theta_max: float = 100.0           # Theta projection radius (l_inf)
    privatizer: PrivatizerConfig = PrivatizerConfig(xi=1.0)
    lr_scale: float = 1.0              # 1.0 == paper-faithful
    init_bank_zero: bool = False       # paper inits all copies to 0
    caps: Optional[Sequence[int]] = None  # per-owner response caps (None = T)
    # DP-FTRL tree-aggregated noise (Kairouz et al. 2021): None = the
    # paper's independent per-round mechanism; d >= 1 = each owner carries
    # a depth-d binary noise tree (AsyncDPState.tree) and every response
    # releases the active-node-sum DELTA, so cumulative noise over t
    # responses is popcount(t) <= d node draws at per-node scale d*b(R),
    # R = min(cap, 2^d - 1). d = 0 is the degenerate tree: bit-for-bit
    # the paper mechanism (parity contract, exercised by tests).
    tree_depth: Optional[int] = None
    # Fault tolerance (see repro.federation.faults): None = the fault
    # layer is OFF and every driver traces the PR-7 program verbatim;
    # a FaultPolicy arms the in-graph guards (payload checksums,
    # non-finite detection, stale rejection) and quarantine windows,
    # and the state gains a FaultState (AsyncDPState.faults).
    fault_policy: Optional[FaultPolicy] = None
    # Asynchronous runtime (see repro.federation.staleness): None = no
    # latency/deadline/retry/decay concept and the drivers trace the
    # fault-armed program verbatim; a StalenessPolicy adds the TIMEOUT
    # outcome, per-owner retry-with-backoff counters and the
    # decay**age inertia weight, and the state gains a StalenessState
    # (AsyncDPState.stale). Requires fault_policy (TIMEOUT lives in the
    # fault algebra) — a never-quarantine policy changes nothing.
    staleness: Optional[StalenessPolicy] = None

    @property
    def n_total(self) -> int:
        return sum(self.owner_sizes)

    @property
    def effective_caps(self) -> Tuple[int, ...]:
        if self.caps is None:
            return (self.horizon,) * self.n_owners
        return tuple(self.caps)


class AsyncDPState(NamedTuple):
    theta_L: Any                       # central model pytree
    bank: Any                          # same pytree, leaves (N, ...)
    step: jax.Array                    # () int32
    # Device-resident budget counters (see privacy.DeviceLedger). The
    # per-round step() leaves it untouched (host authorization); the fused
    # multi-round driver spends/refuses in-graph.
    ledger: Optional[DeviceLedger] = None
    # Device-resident DP-FTRL noise trees (TreeNoise) when
    # cfg.tree_depth is set; None for the independent-noise mechanisms.
    tree: Optional[Any] = None
    # Device-resident fault-layer arrays (faults.FaultState) when
    # cfg.fault_policy is set: per-owner bank-row checksums, fault
    # windows, quarantine flags. None = fault layer off.
    faults: Optional[FaultState] = None
    # Device-resident async-runtime counters (staleness.StalenessState)
    # when cfg.staleness is set: round clock, per-owner last-grant ages,
    # backoff cooldowns, retry budgets. None = runtime layer off.
    stale: Optional[StalenessState] = None


@jax.tree_util.register_pytree_node_class
class TreeNoise:
    """Per-owner DP-FTRL noise-tree state (device-resident).

    `nodes` holds every owner's live node values: for flat states one
    (N_owners, depth, P) f32 matrix; for pytree states the model pytree
    with (N_owners, depth, *leaf.shape) f32 leaves (ALWAYS f32 — the
    noise calibration must not be laundered through a bf16 model dtype).
    `counts` is (N_owners,) int32 leaves released so far — the online
    binary counter whose bit pattern determines which nodes retire and
    which level holds the fresh draw at each increment. `depth` is static
    pytree metadata (it selects the traced program).
    """

    def __init__(self, nodes: Any, counts: jax.Array, depth: int):
        self.nodes = nodes
        self.counts = counts
        self.depth = depth

    def tree_flatten(self):
        return (self.nodes, self.counts), self.depth

    @classmethod
    def tree_unflatten(cls, depth, children):
        return cls(*children, depth=depth)

    def replace(self, **kw) -> "TreeNoise":
        fields = {"nodes": self.nodes, "counts": self.counts,
                  "depth": self.depth}
        fields.update(kw)
        return TreeNoise(**fields)


def init_tree_noise(cfg: AsyncDPConfig, theta_L) -> Optional[TreeNoise]:
    """Fresh (all-zero) noise trees matching `theta_L`'s representation;
    None when cfg.tree_depth is None (independent-noise mechanisms)."""
    if cfg.tree_depth is None:
        return None
    d, n = cfg.tree_depth, cfg.n_owners
    if isinstance(theta_L, ParamFlat):
        nodes = jnp.zeros((n, d, theta_L.size), jnp.float32)
    else:
        nodes = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros((n, d) + leaf.shape, jnp.float32), theta_L)
    return TreeNoise(nodes, jnp.zeros((n,), jnp.int32), d)


def _tree_row_of(tree: TreeNoise, owner_idx, row_idx=None):
    """Gather one owner's (depth, ...) node row + its leaf count.

    `row_idx` separates the NODE-ROW index from the COUNTER index for
    paged states (nodes page with the bank's hot slots, (n_hot, d, P);
    the leaf counters stay a per-owner (N,) column). None keeps both
    equal to `owner_idx` — the non-paged trace, verbatim."""
    ridx = owner_idx if row_idx is None else row_idx
    with jax.named_scope("fed.noise"):       # the noise state's own read
        row = jax.tree_util.tree_map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, ridx, 0,
                                                      keepdims=False),
            tree.nodes)
        return row, tree.counts[owner_idx]


def _tree_write(tree: TreeNoise, new_row, owner_idx, grant=1,
                row_idx=None) -> TreeNoise:
    """Scatter an owner's node row back and bump its leaf counter by
    `grant` (0/1 — the fused driver passes the grant bit; callers mask
    `new_row` back to the old row on refusal, so a refused round is a
    bit-exact no-op on the whole tree). `row_idx` (paged states) puts
    the node scatter at the hot slot while the counter stays per-owner."""
    ridx = owner_idx if row_idx is None else row_idx
    nodes = jax.tree_util.tree_map(
        lambda leaf, v: jax.lax.dynamic_update_index_in_dim(leaf, v,
                                                            ridx, 0),
        tree.nodes, new_row)
    return tree.replace(nodes=nodes,
                        counts=tree.counts.at[owner_idx].add(grant))


def _init_staleness(cfg: AsyncDPConfig) -> Optional[StalenessState]:
    """Fresh runtime counters when cfg.staleness is armed; refuses a
    staleness config without the fault layer (TIMEOUT is a fault code,
    and every driver's staleness algebra lives in its faulted body)."""
    if cfg.staleness is None:
        return None
    if cfg.fault_policy is None:
        raise ValueError(
            "cfg.staleness rides on the fault algebra (TIMEOUT is a fault "
            "code); arm cfg.fault_policy too — a never-quarantine "
            "FaultPolicy(max_faults=2**30, window=2**30) changes nothing")
    return init_staleness_state(cfg.n_owners, cfg.staleness)


def init_state(params, cfg: AsyncDPConfig) -> AsyncDPState:
    if cfg.init_bank_zero:
        params = jax.tree_util.tree_map(jnp.zeros_like, params)
    bank = jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf[None], (cfg.n_owners,) + leaf.shape), params)
    faults = (None if cfg.fault_policy is None
              else init_fault_state(bank, cfg.n_owners))
    return AsyncDPState(params, bank, jnp.zeros((), jnp.int32),
                        make_device_ledger(cfg.effective_caps),
                        init_tree_noise(cfg, params), faults,
                        _init_staleness(cfg))


def init_state_flat(params, cfg: AsyncDPConfig,
                    bank_dtype=None, mesh=None) -> AsyncDPState:
    """Flat-buffer state: theta_L is a ParamFlat (one contiguous (P,) f32
    buffer) and the owner bank is a single (N_owners, P) matrix, so bank
    gather/scatter is one row slice instead of per-leaf dynamic indexing.
    Both drivers accept either state kind and dispatch on it.

    `bank_dtype` (None = float32) narrows the bank STORAGE only — e.g.
    bf16 halves the N*P resident bytes and the fused scan's loop-carry
    traffic; rows upcast to f32 on gather. The strings "int8"/"fp8" (or
    a flatten.BankCodec) build a QUANTIZED bank instead: 1-byte codes +
    per-row f32 scales + an error-feedback residual row, ~4x below f32
    (rows decode on gather; granted rounds re-encode with stochastic
    rounding driven by the round key). f32 keeps the bit-parity
    contract with the tree path.

    `mesh` (None = single-device) lays the state out under the
    repro.sharding.rules.flat_shardings rules: bank rows over the data
    axes, P like the model, ledger counters replicated. Pass the same
    mesh to the driver builders so the scan bodies keep the layout."""
    if cfg.init_bank_zero:
        params = jax.tree_util.tree_map(jnp.zeros_like, params)
    flat = pack_params(params)
    ledger = make_device_ledger(cfg.effective_caps)
    tree = init_tree_noise(cfg, flat)
    if mesh is None:
        bank = init_flat_bank(flat, cfg.n_owners, bank_dtype)
    else:
        if (mesh.devices.size > 1
                and not jax.config.jax_threefry_partitionable):
            import warnings
            warnings.warn(
                "multi-device federation without "
                "jax_threefry_partitionable: the legacy threefry lowering "
                "re-associates counters under SPMD partitioning, so noise "
                "draws (still lawful Laplace samples) differ from the "
                "single-device program under the same keys; enable "
                "jax.config.update('jax_threefry_partitionable', True) "
                "for topology-independent draws", UserWarning,
                stacklevel=2)
        from repro.sharding.rules import flat_shardings
        sh = flat_shardings(mesh, cfg.n_owners, flat.size)
        flat = ParamFlat(jax.device_put(flat.buf, sh.theta), flat.spec)
        bank = init_flat_bank(flat, cfg.n_owners, bank_dtype,
                              sharding=sh.bank,
                              scales_sharding=sh.bank_scales,
                              residual_sharding=sh.row)
        ledger = jax.device_put(ledger, sh.ledger)
        if tree is not None:
            tree = TreeNoise(jax.device_put(tree.nodes, sh.tree_nodes),
                             jax.device_put(tree.counts, sh.ledger),
                             tree.depth)
    faults = (None if cfg.fault_policy is None
              else init_fault_state(bank, cfg.n_owners))
    if faults is not None and mesh is not None:
        from repro.sharding.rules import flat_shardings
        sh = flat_shardings(mesh, cfg.n_owners, flat.size)
        faults = jax.device_put(faults, sh.faults)
    stale = _init_staleness(cfg)
    if stale is not None and mesh is not None:
        # per-owner (N,) runtime counters replicate like the ledger
        stale = jax.device_put(stale, sh.ledger)
    return AsyncDPState(flat, bank, jnp.zeros((), jnp.int32), ledger, tree,
                        faults, stale)


def _flat_shardings_for(mesh, theta_L, bank):
    """FlatShardings for a flat state on `mesh` (None for tree states or
    no mesh). Called at TRACE time — shapes are static there, so the
    divisibility degrades in the rules see the real N and P."""
    if mesh is None or not isinstance(theta_L, ParamFlat):
        return None
    from repro.sharding.rules import flat_shardings
    if isinstance(bank, PagedBank):
        # hot rows shard like bank rows, n_hot standing in for N (the
        # per-owner (N,) counters are replicated either way)
        bank = bank.hot
    n = bank.n_owners if isinstance(bank, QuantBank) else bank.shape[0]
    return flat_shardings(mesh, n, theta_L.size)


def _gather_row(bank: jax.Array, idx, sh) -> jax.Array:
    """Row `idx` of a dense (N, P) bank.

    With the owner rows sharded over the mesh, a dynamic index makes
    GSPMD all-gather the whole bank every round (and on TPU compiles in
    time proportional to P). A masked sum over the row axis instead
    reduces each device's rows locally and all-reduces one (P,) row; it
    holds one nonzero term per element, so the row comes back exactly
    (up to the sign of a zero)."""
    if sh is None or sh.bank.spec[0] is None:
        return jax.lax.dynamic_index_in_dim(bank, idx, 0, keepdims=False)
    hit = (jnp.arange(bank.shape[0]) == idx)[:, None]
    return jnp.sum(jnp.where(hit, bank, jnp.zeros((), bank.dtype)), axis=0,
                   dtype=bank.dtype)


def _constrain(x, sharding):
    """with_sharding_constraint that understands ParamFlat and None."""
    if sharding is None:
        return x
    if isinstance(x, ParamFlat):
        return x.replace_buf(
            jax.lax.with_sharding_constraint(x.buf, sharding))
    return jax.lax.with_sharding_constraint(x, sharding)


def _constrain_bank(bank, sh):
    """Pin a bank to the mesh layout: dense (N, P) matrices to sh.bank;
    quantized banks pin codes/scales/residual to their bundle entries.
    Paged banks pin the hot tier recursively (sh was built from n_hot)
    and the page table to the replicated counter rule."""
    if sh is None:
        return bank
    if isinstance(bank, PagedBank):
        return bank.replace(
            hot=_constrain_bank(bank.hot, sh),
            hot_ids=jax.lax.with_sharding_constraint(bank.hot_ids,
                                                     sh.ledger))
    if isinstance(bank, QuantBank):
        return QuantBank(
            jax.lax.with_sharding_constraint(bank.codes, sh.bank),
            jax.lax.with_sharding_constraint(bank.scales, sh.bank_scales),
            jax.lax.with_sharding_constraint(bank.residual, sh.row),
            bank.codec)
    return jax.lax.with_sharding_constraint(bank, sh.bank)


def _constrain_tree(tr, sh):
    """Pin flat TreeNoise nodes to the (N, depth, P) rule; pytree nodes
    and meshless runs pass through."""
    if tr is None or sh is None or getattr(sh, "tree_nodes", None) is None:
        return tr
    if not isinstance(tr.nodes, jax.Array):
        return tr
    return tr.replace(nodes=jax.lax.with_sharding_constraint(
        tr.nodes, sh.tree_nodes))


def _constrain_state(theta_L, bank, tr, sh):
    """Pin a round's theta_L, bank and noise tree to the mesh layout
    (`sh`; None passes them through), each under the scope of the pass
    that wrote it."""
    if sh is None:
        return theta_L, bank, tr
    with jax.named_scope("fed.noise"):
        theta_L = _constrain(theta_L, sh.theta)
        tr = _constrain_tree(tr, sh)
    with jax.named_scope("fed.bank_write"):
        bank = _constrain_bank(bank, sh)
    return theta_L, bank, tr


def _require_tree(cfg: AsyncDPConfig, state: AsyncDPState):
    """The state's TreeNoise when cfg asks for one (raising on states
    built before the tree was configured); None otherwise."""
    if cfg.tree_depth is not None and state.tree is None:
        raise ValueError(
            "cfg.tree_depth is set but the state carries no noise tree; "
            "build the state with init_state / init_state_flat / "
            "Federation.init_state under the same config")
    return state.tree


# --------------------- quantized-bank row round-trip -----------------------
# The codec RNG stream is the round key folded with a fixed salt, so the
# stochastic-rounding draws never collide with (or shift) the privacy
# noise draws inside private_grad — an int8/fp8 run sees the SAME Laplace
# noise as the f32 run under the same keys, isolating quantization as the
# only trajectory difference.
_CODEC_SALT = 0x5142                    # "QB"


def _codec_key(key):
    return jax.random.fold_in(key, _CODEC_SALT)


def _decode_bank_row(bank: QuantBank, owner_idx, pcfg: PrivatizerConfig):
    """Gather one owner row: slice codes+scales, decode to (P,) f32."""
    from repro.kernels.bank_codec.ops import decode_row
    codes = jax.lax.dynamic_index_in_dim(bank.codes, owner_idx, 0,
                                         keepdims=False)
    scales = jax.lax.dynamic_index_in_dim(bank.scales, owner_idx, 0,
                                          keepdims=False)
    return decode_row(codes, scales, bank.codec.fmt,
                      block_elems=bank.codec.block_elems,
                      block_rows=pcfg.kernel_block_rows,
                      interpret=resolve_interpret(pcfg.kernel_interpret))


def _encode_bank_row(bank: QuantBank, value, key,
                     pcfg: PrivatizerConfig):
    """Encode one f32 row (+ the EF residual already folded into `value`)
    -> (codes (P,), scales (nb,), err (P,))."""
    from repro.kernels.bank_codec.ops import encode_row
    return encode_row(value, _codec_key(key), bank.codec.fmt,
                      block_elems=bank.codec.block_elems,
                      block_rows=pcfg.kernel_block_rows,
                      interpret=resolve_interpret(pcfg.kernel_interpret))


def _quant_write(bank, new_i, owner_idx, key,
                 pcfg: PrivatizerConfig, ok=None) -> QuantBank:
    """Scatter a granted owner update into a quantized bank.

    The shared residual row is folded into the value BEFORE encoding
    (error feedback), and the fresh quantization error becomes the next
    residual. `ok` (a traced bool, fused-driver refusal masking) selects
    between the new row and the owner's untouched codes/scales — and
    leaves the residual alone on refusal, so a refused round stays a
    bit-exact no-op on the whole bank. A PagedBank recurses on its hot
    tier — `owner_idx` is then the HOT SLOT the caller resolved."""
    if isinstance(bank, PagedBank):
        return bank.replace(hot=_quant_write(bank.hot, new_i, owner_idx,
                                             key, pcfg, ok=ok))
    codes_n, scales_n, err = _encode_bank_row(bank, new_i + bank.residual,
                                              key, pcfg)
    if ok is None:
        residual = err
    else:
        codes_o = jax.lax.dynamic_index_in_dim(bank.codes, owner_idx, 0,
                                               keepdims=False)
        scales_o = jax.lax.dynamic_index_in_dim(bank.scales, owner_idx, 0,
                                                keepdims=False)
        codes_n = jnp.where(ok, codes_n, codes_o)
        scales_n = jnp.where(ok, scales_n, scales_o)
        residual = jnp.where(ok, err, bank.residual)
    return QuantBank(
        jax.lax.dynamic_update_index_in_dim(bank.codes, codes_n,
                                            owner_idx, 0),
        jax.lax.dynamic_update_index_in_dim(bank.scales, scales_n,
                                            owner_idx, 0),
        residual, bank.codec)


def _noise_scales(cfg: AsyncDPConfig) -> jnp.ndarray:
    """Theorem-1 scale per owner (for the averaged clipped gradient).

    Under the tree mechanism (cfg.tree_depth = d >= 1) this is the
    PER-NODE scale: each response participates in d node queries over a
    horizon of at most R = effective cap responses, so Laplace
    composition gives b_node = d * b_theorem1(R). depth 0 degenerates to
    the paper scale exactly (levels = 1, horizon = T)."""
    from repro.federation.privacy import laplace_scale_theorem1
    levels = cfg.tree_depth if cfg.tree_depth else 1
    horizons = (cfg.effective_caps if cfg.tree_depth
                else (cfg.horizon,) * cfg.n_owners)
    return jnp.asarray([
        levels * laplace_scale_theorem1(cfg.xi, h, n_i, e)
        for h, n_i, e in zip(horizons, cfg.owner_sizes, cfg.epsilons)],
        jnp.float32)


def _round_math(loss_fn, cfg: AsyncDPConfig, scales: Optional[jax.Array]):
    """The paper's inertia round (eqs. 5-7), shared VERBATIM between the
    per-round step and the fused multi-round driver so both trace the exact
    same op sequence (bit-for-bit equivalence under fixed keys).

    Returns compute(theta_L, bank, batch, owner_idx, key, tree_row=None,
    tree_count=None) -> (new_L, new_i, theta_i, metrics, new_tree_row).
    The bank-gather-free core is exposed as
    `compute.inner(theta_L, theta_i, batch, owner_idx, key, noise_extra)`:
    the flat engine's reference mode traces that SAME function on its
    unpacked buffers, which is what makes flat-vs-tree bit parity hold.
    `noise_extra` (None for the independent mechanisms) is the DP-FTRL
    retired-node correction added to the response; when it is given,
    inner also returns the fresh Laplace draw so the caller can install
    it as the tree's new node WITHOUT re-consuming the round key."""
    scales = _noise_scales(cfg) if scales is None else jnp.asarray(
        scales, jnp.float32)
    n_i = jnp.asarray(cfg.owner_sizes, jnp.float32)
    n = float(cfg.n_total)
    N, T = cfg.n_owners, cfg.horizon
    lr_own, lr_L = paper_rates(N, T, cfg.rho, cfg.sigma, cfg.lr_scale)

    def project(tree):
        return jax.tree_util.tree_map(
            lambda leaf: jnp.clip(leaf, -cfg.theta_max, cfg.theta_max), tree)

    def inner(theta_L, theta_i, batch, owner_idx, key, noise_extra=None):
        with jax.named_scope("fed.noise"):
            theta_bar = jax.tree_util.tree_map(
                lambda a, b: 0.5 * (a + b), theta_L, theta_i)         # (6)

        if noise_extra is None:
            qbar, pm = private_grad(loss_fn, theta_bar, batch, key,
                                    cfg=cfg.privatizer,
                                    noise_scale=scales[owner_idx])    # (3)+(4)
            zeta = None
        else:
            # tree mechanism: the response carries zeta - sum(retired
            # nodes); `noise_extra` IS that retired-node sum (negated),
            # and the fresh draw comes back so the caller installs it as
            # the new node from the SAME single key consumption.
            qbar, pm, zeta = private_grad(loss_fn, theta_bar, batch, key,
                                          cfg=cfg.privatizer,
                                          noise_scale=scales[owner_idx],
                                          return_noise=True)
            with jax.named_scope("fed.noise"):
                qbar = jax.tree_util.tree_map(
                    lambda q, e: (q.astype(jnp.float32) + e).astype(q.dtype),
                    qbar, noise_extra)
        with jax.named_scope("fed.noise"):
            g_reg = jax.tree_util.tree_map(
                lambda leaf: cfg.sigma * leaf.astype(jnp.float32),
                theta_bar)                                         # grad g
            w_i = n_i[owner_idx] / n
            new_i = project(jax.tree_util.tree_map(
                lambda tb, gg, q: tb - lr_own * (gg / (2 * N)
                                                 + w_i * q.astype(jnp.float32)
                                                 ).astype(tb.dtype),
                theta_bar, g_reg, qbar))                               # (5)
            new_L = project(jax.tree_util.tree_map(
                lambda tb, gg: tb - (lr_L * gg).astype(tb.dtype),
                theta_bar, g_reg))                                     # (7)
        metrics = {"clip_frac": pm["clip_frac"],
                   "max_grad_norm": pm["max_grad_norm"],
                   "grad_noise_scale": scales[owner_idx]}
        return new_L, new_i, metrics, zeta

    def compute(theta_L, bank, batch, owner_idx, key,
                tree_row=None, tree_count=None, row_idx=None,
                stale_w=None):
        if isinstance(bank, PagedBank):
            raise TypeError(
                "PagedBank needs the flat engine (paging.init_paged_state "
                "builds ParamFlat states); the pytree path cannot page")
        del row_idx                 # pytree banks index rows by owner
        with jax.named_scope("fed.bank_read"):
            theta_i = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(
                    leaf, owner_idx, 0, keepdims=False), bank)
        # decayed inertia target (staleness.staleness_weight): the round
        # runs against a copy pulled toward theta_L, but the RAW row is
        # what comes back — masked rounds write it back verbatim. The
        # hook is statically absent at decay=1 (verbatim trace).
        with jax.named_scope("fed.noise"):
            theta_eff = (theta_i if stale_w is None else
                         jax.tree_util.tree_map(
                             lambda l, i: (l.astype(jnp.float32) + stale_w
                                           * (i.astype(jnp.float32)
                                              - l.astype(jnp.float32))
                                           ).astype(i.dtype),
                             theta_L, theta_i))
        d = cfg.tree_depth
        if tree_row is None or not d:
            # no tree, or the degenerate depth-0 tree: the round IS the
            # independent-noise round (bit-for-bit — parity contract)
            new_L, new_i, metrics, _ = inner(theta_L, theta_eff, batch,
                                             owner_idx, key)
            return new_L, new_i, theta_i, metrics, tree_row
        if cfg.privatizer.fused_kernel:
            raise ValueError(
                "tree mechanism with fused_kernel needs the flat engine "
                "(init_state_flat) — the pytree path's fused privatizer "
                "adds its noise in-kernel and cannot split out the draw")
        from repro.kernels.tree_noise.ref import tree_masks_ref

        def bcast(m, leaf):
            return m.reshape((d,) + (1,) * (leaf.ndim - 1))

        with jax.named_scope("fed.noise"):
            retired, fresh = tree_masks_ref(tree_count, d)    # (d,) bools
            extra = jax.tree_util.tree_map(
                lambda nd: -jnp.sum(jnp.where(bcast(retired, nd), nd, 0.0),
                                    axis=0), tree_row)
        new_L, new_i, metrics, zeta = inner(theta_L, theta_eff, batch,
                                            owner_idx, key,
                                            noise_extra=extra)
        with jax.named_scope("fed.noise"):
            new_row = jax.tree_util.tree_map(
                lambda nd, z: jnp.where(
                    bcast(fresh, nd), z[None].astype(jnp.float32),
                    jnp.where(bcast(retired, nd), 0.0, nd)),
                tree_row, zeta)
        return new_L, new_i, theta_i, metrics, new_row

    compute.inner = inner
    return compute


def _flat_clipped_grad_acc(loss_fn, spec: FlatSpec, pcfg: PrivatizerConfig,
                           tb: jax.Array, batch, mesh=None):
    """Sum of per-group clipped (P,) gradients at theta_bar + group gain.

    The gradient is the ordinary tree gradient at `spec.unpack(tb)` packed
    into ONE concat (cheaper than differentiating through the unpack,
    whose transpose pads every leaf cotangent to (P,)); per-group clip
    norms run through the blockwise Pallas squared-norm pass (jnp oracle
    off-TPU). Returns (acc, gain, metrics) with the group-mean divide
    DEFERRED into `gain` so dp_round can fuse it with the noise add and
    the inertia updates.
    """
    from repro.kernels.dp_clip_noise.ops import fused_sqnorm_tree
    interp = resolve_interpret(pcfg.kernel_interpret)
    with jax.named_scope("fed.grad"):
        tb_tree = spec.unpack(tb)

    def flat_grad(mb):
        with jax.named_scope("fed.grad"):
            return spec.pack(jax.grad(loss_fn)(tb_tree, mb))   # (P,)

    def clip(g):
        # (norm, clip scale) of one group's gradient
        norm = jnp.sqrt(fused_sqnorm_tree(g, block_rows=pcfg.kernel_block_rows,
                                          interpret=interp, mesh=mesh))
        return norm, jnp.minimum(1.0, pcfg.xi / jnp.maximum(norm, 1e-12))

    if pcfg.granularity == "example":
        B = jax.tree_util.tree_leaves(batch)[0].shape[0]
        grads = jax.vmap(lambda ex: flat_grad(
            jax.tree_util.tree_map(lambda a: a[None], ex)))(batch)  # (B, P)
        with jax.named_scope("fed.clip"):
            norms = jnp.sqrt(jnp.sum(jnp.square(grads), axis=1))
            scale = jnp.minimum(1.0, pcfg.xi / jnp.maximum(norms, 1e-12))
            acc = jnp.sum(grads * scale[:, None], axis=0)
        return acc, 1.0 / B, {
            "clip_frac": jnp.mean((norms > pcfg.xi).astype(jnp.float32)),
            "max_grad_norm": jnp.max(norms)}
    if pcfg.granularity != "microbatch":
        raise ValueError(pcfg.granularity)

    G = pcfg.n_microbatches
    B = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if not pcfg.pre_grouped:
        assert B % G == 0, (B, G)

    if G == 1:
        # single-group fast path: no scan wrapper, no accumulator init
        mb = (jax.tree_util.tree_map(lambda a: a[0], batch)
              if pcfg.pre_grouped else batch)
        g = flat_grad(mb)
        with jax.named_scope("fed.clip"):
            norm, s = clip(g)
            acc = g * s
        return acc, 1.0, {
            "clip_frac": (norm > pcfg.xi).astype(jnp.float32),
            "max_grad_norm": norm}

    def body(carry, mb):
        acc, nclip, mx = carry
        g = flat_grad(mb)
        with jax.named_scope("fed.clip"):
            norm, s = clip(g)
            return (acc + g * s, nclip + (norm > pcfg.xi),
                    jnp.maximum(mx, norm)), None

    xs = batch if pcfg.pre_grouped else _group_batch(batch, G)
    with jax.named_scope("fed.clip"):
        acc0 = jnp.zeros_like(tb)
    (acc, nclip, mx), _ = jax.lax.scan(
        body, (acc0, jnp.zeros((), jnp.float32),
               jnp.zeros((), jnp.float32)), xs)
    return acc, 1.0 / G, {"clip_frac": nclip / G, "max_grad_norm": mx}


def _round_math_flat(loss_fn, cfg: AsyncDPConfig, scales: Optional[jax.Array],
                     tree_inner, mesh=None):
    """The same inertia round over the flat representation.

    With `privatizer.fused_kernel=False` this is the REFERENCE mode: the
    owner's bank row is gathered as ONE (P,) slice, theta_L and the row are
    unpacked behind an optimization barrier, and the round runs the
    IDENTICAL `tree_inner` trace as the tree path (same per-leaf RNG
    splits, same op sequence — the barrier keeps XLA from re-fusing the
    slice views into it), so results are bit-for-bit `spec.pack()` of the
    tree path's output for f32 models under the same per-round keys.

    With `fused_kernel=True` the gradient is taken directly w.r.t. the
    flat buffer and the whole post-gradient round — group mean, Laplace
    add, eqs. (5)/(7), projection — is ONE `dp_round` Pallas pass over the
    buffer (in-kernel inverse-CDF noise: statistically, not bitwise,
    equivalent — PR 2's kernel contract).
    """
    scales = _noise_scales(cfg) if scales is None else jnp.asarray(
        scales, jnp.float32)
    n_i = jnp.asarray(cfg.owner_sizes, jnp.float32)
    n = float(cfg.n_total)
    N = cfg.n_owners
    lr_own, lr_L = paper_rates(N, cfg.horizon, cfg.rho, cfg.sigma,
                               cfg.lr_scale)
    pcfg = cfg.privatizer

    def compute(theta_L: ParamFlat, bank, batch, owner_idx, key,
                tree_row=None, tree_count=None, row_idx=None,
                stale_w=None):
        spec = theta_L.spec
        sh = _flat_shardings_for(mesh, theta_L, bank)
        d = cfg.tree_depth
        tree_on = tree_row is not None and d          # static (trace-time)
        # paged banks gather from the hot tier at the RESOLVED slot;
        # row_idx=None + a non-paged bank leaves the trace verbatim
        # (ridx IS owner_idx). Scales/weights always index by owner.
        hot = bank.hot if isinstance(bank, PagedBank) else bank
        ridx = owner_idx if row_idx is None else row_idx
        with jax.named_scope("fed.bank_read"):
            if isinstance(hot, QuantBank):
                theta_i = _decode_bank_row(hot, ridx, pcfg)        # (P,)
            else:
                theta_i = _gather_row(hot, ridx, sh)                # (P,)
            if sh is not None:
                # the gathered row keeps the bank's P-axis layout (==
                # theta's), so theta_bar and the whole round stay local
                theta_i = jax.lax.with_sharding_constraint(theta_i, sh.row)
        # decayed inertia target — same contract as the pytree path: the
        # round consumes the pulled-in copy, the RAW row is returned for
        # the masked write-backs. Statically absent at decay=1.
        with jax.named_scope("fed.noise"):
            theta_eff = (theta_i if stale_w is None
                         else theta_L.buf + stale_w * (theta_i
                                                       - theta_L.buf))
        if pcfg.fused_kernel:
            if pcfg.mechanism != "laplace":
                raise ValueError(
                    "fused_kernel implements the laplace mechanism")
            from repro.kernels.dp_clip_noise.ops import dp_round_flat
            with jax.named_scope("fed.noise"):
                tb = 0.5 * (theta_L.buf + theta_eff)               # (6)
                ns = scales[owner_idx]
            acc, gain, pm = _flat_clipped_grad_acc(loss_fn, spec, pcfg,
                                                   tb, batch, mesh)
            with jax.named_scope("fed.noise"):
                if tree_on:
                    # tree mechanism: the round key feeds ONLY the tree op
                    # (the fresh node draw); the response adds the node
                    # DELTA, then the epilogue repeats dp_round_ref's
                    # exact op order so depth-0 (no node traffic, delta
                    # == draw) stays bit-identical to the dp_round_flat
                    # path.
                    from repro.kernels.tree_noise.ops import tree_delta_row
                    delta, new_row = tree_delta_row(
                        tree_row, tree_count, key, ns,
                        block_rows=min(pcfg.kernel_block_rows, 64),
                        interpret=resolve_interpret(pcfg.kernel_interpret))
                    q = acc * gain + delta                          # (4)
                    g_reg = cfg.sigma * tb
                    new_i = jnp.clip(
                        tb - lr_own * (g_reg * (1.0 / (2 * N))
                                       + (n_i[owner_idx] / n) * q),
                        -cfg.theta_max, cfg.theta_max)              # (5)
                    new_L = jnp.clip(tb - lr_L * g_reg,
                                     -cfg.theta_max, cfg.theta_max)  # (7)
                else:
                    new_L, new_i = dp_round_flat(          # (4)+(5)+(7)+Pi
                        tb, acc, key, gain, ns, n_i[owner_idx] / n,
                        sigma=cfg.sigma, lr_own=lr_own, lr_l=lr_L,
                        n_owners=N, theta_max=cfg.theta_max,
                        block_rows=pcfg.kernel_block_rows,
                        interpret=resolve_interpret(pcfg.kernel_interpret),
                        mesh=mesh)
                    new_row = tree_row
            metrics = {"clip_frac": pm["clip_frac"],
                       "max_grad_norm": pm["max_grad_norm"],
                       "grad_noise_scale": ns}
        else:
            # reference mode: the unpack, pack and node bookkeeping around
            # tree_inner are the round's own passes; tree_inner scopes
            # its gradient, clipping and noise itself
            with jax.named_scope("fed.noise"):
                if tree_on:
                    from repro.kernels.tree_noise.ref import tree_masks_ref
                    retired, fresh = tree_masks_ref(tree_count, d)
                    extra = spec.unpack_f32(
                        -jnp.sum(jnp.where(retired[:, None], tree_row, 0.0),
                                 axis=0))
                else:
                    extra = None
                try:
                    tl_tree, ti_tree = jax.lax.optimization_barrier(
                        (spec.unpack(theta_L.buf), spec.unpack(theta_eff)))
                except NotImplementedError:
                    # no batching rule for the barrier (vmapped by the
                    # owner-parallel grouped driver). The barrier is
                    # semantically identity — only an anti-fusion hint
                    # that protects the scan-carry BIT-parity contract,
                    # which the grouped mode does not promise for groups
                    # > 1 anyway.
                    tl_tree, ti_tree = (spec.unpack(theta_L.buf),
                                        spec.unpack(theta_eff))
            new_L_t, new_i_t, metrics, zeta = tree_inner(
                tl_tree, ti_tree, batch, owner_idx, key,
                noise_extra=extra)
            with jax.named_scope("fed.noise"):
                new_L, new_i = spec.pack(new_L_t), spec.pack(new_i_t)
                if tree_on:
                    zf = spec.pack_f32(zeta)
                    new_row = jnp.where(fresh[:, None], zf[None],
                                        jnp.where(retired[:, None], 0.0,
                                                  tree_row))
                else:
                    new_row = tree_row
        return ParamFlat(new_L, spec), new_i, theta_i, metrics, new_row

    return compute


def _round_compute(loss_fn, cfg: AsyncDPConfig, scales: Optional[jax.Array],
                   mesh=None):
    """Dispatch the round math on the state representation: ParamFlat
    states run the flat engine, pytree states the reference tree path.
    All drivers share this, so one built step function serves either
    state kind (jit specializes per structure)."""
    if cfg.tree_depth is not None:
        if not 0 <= cfg.tree_depth <= 30:
            raise ValueError(
                f"tree_depth must be in [0, 30], got {cfg.tree_depth}")
        if cfg.tree_depth:
            cap_max = (1 << cfg.tree_depth) - 1
            if max(cfg.effective_caps) > cap_max:
                # past 2^d - 1 leaves the online binary counter has no
                # level left for the fresh node and the variance
                # accounting silently breaks — refuse at build time
                raise ValueError(
                    f"depth-{cfg.tree_depth} tree holds {cap_max} leaves "
                    f"but effective caps reach "
                    f"{max(cfg.effective_caps)}; lower cfg.caps or deepen "
                    f"the tree")
    tree_c = _round_math(loss_fn, cfg, scales)
    flat_c = _round_math_flat(loss_fn, cfg, scales, tree_c.inner, mesh=mesh)

    def compute(theta_L, bank, batch, owner_idx, key,
                tree_row=None, tree_count=None, row_idx=None,
                stale_w=None):
        if isinstance(theta_L, ParamFlat):
            return flat_c(theta_L, bank, batch, owner_idx, key,
                          tree_row=tree_row, tree_count=tree_count,
                          row_idx=row_idx, stale_w=stale_w)
        return tree_c(theta_L, bank, batch, owner_idx, key,
                      tree_row=tree_row, tree_count=tree_count,
                      row_idx=row_idx, stale_w=stale_w)

    return compute


def _write_bank(bank, value, owner_idx):
    if isinstance(bank, PagedBank):    # paged: callers pass the HOT SLOT
        return bank.replace(hot=_write_bank(bank.hot, value, owner_idx))
    if isinstance(bank, jax.Array):    # flat (N, P) bank: one row scatter
        return jax.lax.dynamic_update_index_in_dim(
            bank, value.astype(bank.dtype), owner_idx, 0)
    return jax.tree_util.tree_map(
        lambda leaf, v: jax.lax.dynamic_update_index_in_dim(
            leaf, v.astype(leaf.dtype), owner_idx, 0),
        bank, value)


def _bank_slot(bank, owner_idx):
    """(row_idx, hit) for one owner contact.

    Paged banks resolve owner -> hot slot in-graph via the device page
    table (see PagedBank.lookup); the drivers fold `hit` into their
    grant mask, so a non-resident owner is a bit-exact masked no-op.
    Non-paged banks index rows BY OWNER: (None, None) keeps every
    downstream trace verbatim (no lookup op, unconditional grant).
    """
    if isinstance(bank, PagedBank):
        return bank.lookup(owner_idx)
    return None, None


def _bank_is_quant(bank) -> bool:
    """Static: does this bank store quantized rows (possibly paged)?"""
    hot = bank.hot if isinstance(bank, PagedBank) else bank
    return isinstance(hot, QuantBank)


def _require_fault_policy(cfg: AsyncDPConfig, state: AsyncDPState):
    """Trace-time consistency check between the config's fault policy and
    the state's FaultState (both present or both absent)."""
    if state.faults is not None and cfg.fault_policy is None:
        raise ValueError(
            "the state carries fault counters but cfg.fault_policy is "
            "None; build the driver and the state from the same config")
    return cfg.fault_policy


def _require_staleness(cfg: AsyncDPConfig, state: AsyncDPState):
    """Trace-time consistency check between cfg.staleness and the
    state's StalenessState (both armed or both absent)."""
    if (state.stale is None) != (cfg.staleness is None):
        raise ValueError(
            "cfg.staleness and the state's runtime counters must be armed "
            "together; build the driver and the state from the same config")
    if state.stale is not None and state.faults is None:
        raise ValueError(
            "the staleness runtime rides on the fault algebra; the state "
            "must carry a FaultState (arm cfg.fault_policy)")
    return cfg.staleness


def _guarded_round(compute, cfg: AsyncDPConfig, state: AsyncDPState,
                   batch, owner_idx, key, fcode, answered, sh,
                   row_idx=None, stale_w=None):
    """One fault-guarded round, shared by the per-round step and the
    fused scan (scalar `owner_idx`/`fcode`).

    `answered` is the caller's grant bit (ledger-authorized, not
    quarantined, not dropped — and, for paged banks, resident: the
    caller folds the page-table `hit` in, so a miss reaches here
    already masked). The guards verify the owner's resident payload
    against its stored checksum, NaN-poison the update when the round
    carries NONFINITE_GRAD, and reject stale replays; a rejected round
    is a bit-exact no-op on theta/bank/tree (same jnp.where masking as
    ledger refusal) and its rejection bit comes back as
    `metrics["faulted"]` — epsilon for it was already charged at
    response time (see faults module docstring). `row_idx` (paged
    banks) is the resolved hot slot every row gather/scatter uses,
    while checksum/counter columns stay per-owner. A TIMEOUT code fails
    the `deadline_guard` and masks the round like any other guard, but
    lands in `metrics["timed_out"]` instead of `metrics["faulted"]`
    (lateness dominates payload inspection: the learner discards a late
    response unexamined — epsilon spent either way). `stale_w`
    (staleness decay armed) is the round's lambda**age inertia weight,
    handed through to compute.

    Returns (theta_L, bank, tree, faults, metrics, apply, guard_rej,
    timed).
    """
    fs = state.faults
    tr = state.tree
    widx = owner_idx if row_idx is None else row_idx
    row, cnt = (None, None) if tr is None else _tree_row_of(tr, owner_idx,
                                                            row_idx)
    # payload integrity is judged on the PRE-ROUND bank (what the round
    # actually consumed), before any write
    with jax.named_scope("fed.bank_read"):
        payload_ok = _faults.verify_row(fs.checksum, state.bank, owner_idx,
                                        fcode == _faults.CORRUPT_PAYLOAD,
                                        row_idx=row_idx)
    new_L, new_i, theta_i, metrics, new_row = compute(
        state.theta_L, state.bank, batch, owner_idx, key,
        tree_row=row, tree_count=cnt, row_idx=row_idx, stale_w=stale_w)
    with jax.named_scope("fed.noise"):
        new_i = _faults.inject_nonfinite(new_i,
                                         fcode == _faults.NONFINITE_GRAD)
        guard_ok = (payload_ok & _faults.finite_guard((new_i, new_L))
                    & (fcode != _faults.STALE))
        on_time = deadline_guard(fcode)
        apply = answered & guard_ok & on_time
        timed = answered & ~on_time
        guard_rej = answered & on_time & ~guard_ok
        theta_L = jax.tree_util.tree_map(
            lambda nl, ol: jnp.where(apply, nl, ol), new_L, state.theta_L)
    with jax.named_scope("fed.bank_write"):
        if _bank_is_quant(state.bank):
            # same key as compute() by contract: _quant_write folds in
            # _CODEC_SALT, so SR bits never touch the privacy stream
            bank = _quant_write(state.bank, new_i, widx, key,  # dpcheck: ignore[DPC105]
                                cfg.privatizer, ok=apply)
        else:
            bank = _write_bank(
                state.bank,
                jax.tree_util.tree_map(lambda a, b: jnp.where(apply, a, b),
                                       new_i, theta_i),
                widx)
    if tr is not None:
        with jax.named_scope("fed.noise"):
            masked_row = jax.tree_util.tree_map(
                lambda a, b: jnp.where(apply, a, b), new_row, row)
            tr = _tree_write(tr, masked_row, owner_idx,
                             grant=apply.astype(jnp.int32), row_idx=row_idx)
    theta_L, bank, tr = _constrain_state(theta_L, bank, tr, sh)
    # re-derive the stored checksum from the POST-WRITE row; masked
    # rounds drop the scatter, so the stored sum stays in lockstep with
    # the row it describes
    with jax.named_scope("fed.bank_write"):
        fs = _faults.update_checksum(fs, bank, owner_idx, apply,
                                     row_idx=row_idx)
    metrics = dict(metrics)
    metrics.update(faulted=guard_rej, timed_out=timed)
    return theta_L, bank, tr, fs, metrics, apply, guard_rej, timed


def make_train_step(loss_fn, cfg: AsyncDPConfig,
                    scales: Optional[jax.Array] = None, mesh=None):
    """Returns step(state, batch, owner_idx, key) -> (state, metrics).

    loss_fn(params, batch) -> scalar. batch holds ONE owner's microbatch.
    `scales` overrides the per-owner Theorem-1 noise scales (the Federation
    session passes its Mechanism's ledgered scales here); None recomputes
    them from cfg exactly as before. The device ledger (if any) passes
    through untouched — this path is host-authorized.

    States built by `init_state_flat` (ParamFlat theta_L + (N, P) bank) run
    the flat-buffer engine; pytree states run the reference tree path —
    the same returned step function serves both. `mesh` pins flat states
    to the flat_shardings layout (see module docstring).
    """
    compute = _round_compute(loss_fn, cfg, scales, mesh=mesh)

    def step(state: AsyncDPState, batch, owner_idx: jax.Array, key,
             fault_code=None) -> Tuple[AsyncDPState, Dict]:
        tr = _require_tree(cfg, state)
        sh = _flat_shardings_for(mesh, state.theta_L, state.bank)
        with jax.named_scope("fed.bank_read"):
            slot, hit = _bank_slot(state.bank, owner_idx)
        if state.faults is not None:
            # fault-armed state: host-side the session has already
            # handled DROP and quarantine (neither reaches the step), so
            # the round is answered and only the in-graph guards decide.
            # Paged states additionally gate on page residency — a miss
            # (the pager failed its prefetch contract) is a masked no-op
            answered = jnp.bool_(True) if hit is None else hit
            policy = _require_fault_policy(cfg, state)
            spolicy = _require_staleness(cfg, state)
            ss = state.stale
            fcode = (jnp.int8(_faults.OK) if fault_code is None
                     else jnp.asarray(fault_code, jnp.int8))
            stale_w = None
            if ss is not None and spolicy.decay != 1.0:
                stale_w = staleness_weight(ss, owner_idx, ss.clock, spolicy)
            theta_L, bank, tr, fs, metrics, apply, guard_rej, timed = \
                _guarded_round(compute, cfg, state, batch, owner_idx, key,
                               fcode, answered, sh, row_idx=slot,
                               stale_w=stale_w)
            fs = _faults.fault_tick(fs, owner_idx, guard_rej, policy,
                                    active=answered)
            if ss is not None:
                # dispatched rounds are never retries (the session masks
                # cooldown rounds host-side, before the step is called)
                ss = staleness_tick(ss, owner_idx, ss.clock,
                                    is_retry=jnp.bool_(False), apply=apply,
                                    timed=timed, policy=spolicy,
                                    active=jnp.bool_(True), ticks=1)
            return AsyncDPState(theta_L, bank,
                                state.step + apply.astype(jnp.int32),
                                state.ledger, tr, fs, ss), metrics
        if fault_code is not None:
            raise ValueError(
                "fault injection needs a fault-armed state; build the "
                "config with fault_policy=FaultPolicy(...)")
        row, cnt = (None, None) if tr is None else _tree_row_of(tr,
                                                                owner_idx,
                                                                slot)
        new_L, new_i, theta_i, metrics, new_row = compute(
            state.theta_L, state.bank, batch, owner_idx, key,
            tree_row=row, tree_count=cnt, row_idx=slot)
        if hit is not None:
            # paged, host-authorized: no ledger in-graph, so residency is
            # the only grant bit — a miss masks every write bit-exactly
            with jax.named_scope("fed.noise"):
                new_L = jax.tree_util.tree_map(
                    lambda nl, ol: jnp.where(hit, nl, ol), new_L,
                    state.theta_L)
        widx = owner_idx if slot is None else slot
        with jax.named_scope("fed.bank_write"):
            if _bank_is_quant(state.bank):
                # same key as compute() by contract: _quant_write folds in
                # _CODEC_SALT, so SR bits never touch the privacy stream
                bank = _quant_write(state.bank, new_i, widx, key,  # dpcheck: ignore[DPC105]
                                    cfg.privatizer, ok=hit)
            else:
                value = (new_i if hit is None
                         else jnp.where(hit, new_i, theta_i))
                bank = _write_bank(state.bank, value, widx)
        if tr is not None:
            # host-authorized path: the round always counts (refusal
            # happens before step() is called), so the leaf lands unless
            # a paged state missed
            with jax.named_scope("fed.noise"):
                if hit is None:
                    tr = _tree_write(tr, new_row, owner_idx)
                else:
                    masked_row = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(hit, a, b), new_row, row)
                    tr = _tree_write(tr, masked_row, owner_idx,
                                     grant=hit.astype(jnp.int32),
                                     row_idx=slot)
        new_L, bank, tr = _constrain_state(new_L, bank, tr, sh)
        bump = 1 if hit is None else hit.astype(jnp.int32)
        return AsyncDPState(new_L, bank, state.step + bump,
                            state.ledger, tr, state.faults,
                            state.stale), metrics

    return step


def make_fused_rounds(loss_fn, cfg: AsyncDPConfig,
                      scales: Optional[jax.Array] = None, mesh=None,
                      unroll: int = 1):
    """Device-resident multi-round driver: K rounds in ONE dispatch.

    Returns run(state, batches, owner_seq, keys) -> (state, metrics) where
    every batch leaf carries a leading (K,) round axis, owner_seq is (K,)
    int32, keys is (K,) PRNG keys, and metrics are stacked (K,) arrays.

    Authorization is in-graph: round k is granted iff
    `state.ledger.spent[i_k] < cap[i_k]` at that point of the scan. A
    refused round is a no-op on model state EXACTLY as the host-authorized
    per-round path — the computed update is discarded with `jnp.where`, the
    owner's own copy is written back unchanged, and the refusal lands in
    `ledger.refused` for `Federation.reconcile()` to fold into the host
    accountant. Granted rounds run the exact same `_round_math` trace as
    `make_train_step`, so a fused schedule reproduces the per-round loop
    bit-for-bit under the same per-round keys. Flat states (see
    `init_state_flat`) run the flat-buffer engine inside the same scan.
    `mesh` pins flat states to the flat_shardings layout: the constraint
    sits INSIDE the scan body, so the carry stays distributed across all
    K rounds (no per-round gather, no host transfer of the bank).
    `unroll` is handed to the lax.scan (identical values at any setting):
    unrolled blocks amortize the loop-carry bank copy XLA:CPU pays per
    scan iteration — measured +24% at unroll=4 at the MLP-scale config.
    Quantized banks (QuantBank states) decode the owner row on gather and
    re-encode granted updates with stochastic rounding + error feedback;
    refused rounds leave codes/scales/residual untouched.
    """
    compute = _round_compute(loss_fn, cfg, scales, mesh=mesh)

    def body(state: AsyncDPState, xs):
        batch, owner_idx, key = xs
        led = state.ledger
        tr = state.tree
        sh = _flat_shardings_for(mesh, state.theta_L, state.bank)
        with jax.named_scope("fed.bank_read"):
            slot, hit = _bank_slot(state.bank, owner_idx)
        with jax.named_scope("fed.ledger"):
            ok = led.authorized(owner_idx)
            if hit is not None:
                # paged: residency folds into the grant BEFORE the ledger
                # update — a miss spends nothing and lands in `refused`
                # (lawful: no epsilon without a response; in a correctly
                # prefetched session misses never occur, so a nonzero
                # refused count under an authorized schedule flags a
                # pager bug, not a privacy event)
                ok = ok & hit
            oki = ok.astype(jnp.int32)
        row, cnt = (None, None) if tr is None else _tree_row_of(tr,
                                                                owner_idx,
                                                                slot)
        new_L, new_i, theta_i, metrics, new_row = compute(
            state.theta_L, state.bank, batch, owner_idx, key,
            tree_row=row, tree_count=cnt, row_idx=slot)
        with jax.named_scope("fed.noise"):
            theta_L = jax.tree_util.tree_map(
                lambda nl, ol: jnp.where(ok, nl, ol), new_L, state.theta_L)
        widx = owner_idx if slot is None else slot
        with jax.named_scope("fed.bank_write"):
            if _bank_is_quant(state.bank):
                bank = _quant_write(state.bank, new_i, widx, key,
                                    cfg.privatizer, ok=ok)
            else:
                bank = _write_bank(
                    state.bank,
                    jax.tree_util.tree_map(lambda a, b: jnp.where(ok, a, b),
                                           new_i, theta_i),
                    widx)
        if tr is not None:
            # refusal masking: the old row is written back and the leaf
            # counter bumps by the grant bit, so a refused round is a
            # bit-exact no-op on the tree (same contract as the bank)
            with jax.named_scope("fed.noise"):
                masked_row = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new_row, row)
                tr = _tree_write(tr, masked_row, owner_idx, grant=oki,
                                 row_idx=slot)
        theta_L, bank, tr = _constrain_state(theta_L, bank, tr, sh)
        with jax.named_scope("fed.ledger"):
            ledger = led.replace(
                spent=led.spent.at[owner_idx].add(oki),
                refused=led.refused.at[owner_idx].add(1 - oki))
        metrics = dict(metrics)
        metrics.update(refused=~ok, owner=owner_idx)
        return AsyncDPState(theta_L, bank, state.step + oki, ledger,
                            tr, state.faults, state.stale), metrics

    def body_faulted(state: AsyncDPState, xs):
        # fault-armed scan round: same algebra as the per-round step's
        # faulted branch, with ledger authorization and quarantine
        # resolved in-graph. Epsilon is charged at response time: spent
        # counts every ANSWERED round (guard-rejected and timed-out ones
        # included), a DROP before the answer spends nothing
        # (ledger.dropped), and quarantined rounds are masked without
        # refusal accounting (ledger.quarantined). With the staleness
        # runtime armed, an owner whose backoff cooldown is live is a
        # masked RE-DISPATCH (ledger.retried, no epsilon — the learner
        # never sent the query); precedence is quarantine > backoff >
        # budget > drop.
        batch, owner_idx, key, fcode = xs
        led = state.ledger
        fs = state.faults
        ss = state.stale
        policy = cfg.fault_policy
        spolicy = cfg.staleness
        sh = _flat_shardings_for(mesh, state.theta_L, state.bank)
        with jax.named_scope("fed.bank_read"):
            slot, hit = _bank_slot(state.bank, owner_idx)
        quar = fs.quarantined[owner_idx]
        with jax.named_scope("fed.ledger"):
            led_auth = led.authorized(owner_idx)
            if hit is not None:
                # paged: a page miss refuses like budget exhaustion
                # (spends nothing, counts in `refused` unless
                # quarantined) — see the plain body
                led_auth = led_auth & hit
        if ss is not None:
            in_backoff = ss.cooldown[owner_idx] > 0
            is_retry = ~quar & in_backoff
            avail = ~quar & ~in_backoff
        else:
            is_retry = None
            avail = ~quar
        auth = led_auth & avail
        is_drop = fcode == _faults.DROP
        answered = auth & ~is_drop
        stale_w = None
        if ss is not None and spolicy.decay != 1.0:
            stale_w = staleness_weight(ss, owner_idx, ss.clock, spolicy)
        theta_L, bank, tr, fs, metrics, apply, guard_rej, timed = \
            _guarded_round(compute, cfg, state, batch, owner_idx, key,
                           fcode, answered, sh, row_idx=slot,
                           stale_w=stale_w)
        with jax.named_scope("fed.ledger"):
            upd = dict(
                spent=led.spent.at[owner_idx].add(
                    answered.astype(jnp.int32)),
                refused=led.refused.at[owner_idx].add(
                    (avail & ~led_auth).astype(jnp.int32)),
                dropped=led.dropped.at[owner_idx].add(
                    (auth & is_drop).astype(jnp.int32)),
                faulted=led.faulted.at[owner_idx].add(
                    guard_rej.astype(jnp.int32)),
                quarantined=led.quarantined.at[owner_idx].add(
                    quar.astype(jnp.int32)),
                timed_out=led.timed_out.at[owner_idx].add(
                    timed.astype(jnp.int32)))
            if ss is not None:
                upd["retried"] = led.retried.at[owner_idx].add(
                    is_retry.astype(jnp.int32))
            ledger = led.replace(**upd)
        # timeouts and retries are NOT quarantine events: slowness has
        # its own escalation path (backoff); a backed-off round is not
        # even a contact (the learner never dispatched)
        fs = _faults.fault_tick(fs, owner_idx, guard_rej | (auth & is_drop),
                                policy, active=avail)
        metrics.update(refused=avail & ~led_auth, dropped=auth & is_drop,
                       quarantined=quar, owner=owner_idx)
        if ss is not None:
            metrics.update(retried=is_retry)
            ss = staleness_tick(ss, owner_idx, ss.clock, is_retry=is_retry,
                                apply=apply, timed=timed, policy=spolicy,
                                active=jnp.bool_(True), ticks=1)
        return AsyncDPState(theta_L, bank,
                            state.step + apply.astype(jnp.int32),
                            ledger, tr, fs, ss), metrics

    def run(state: AsyncDPState, batches, owner_seq, keys,
            fault_codes=None):
        if state.ledger is None:
            raise ValueError(
                "fused rounds need a device ledger on the state; build the "
                "state with init_state / Federation.init_state")
        _require_tree(cfg, state)
        if state.faults is None:
            if fault_codes is not None:
                raise ValueError(
                    "fault codes need a fault-armed state; build the "
                    "config with fault_policy=FaultPolicy(...)")
            return jax.lax.scan(body, state, (batches, owner_seq, keys),
                                unroll=unroll)
        _require_fault_policy(cfg, state)
        _require_staleness(cfg, state)
        if fault_codes is None:
            fault_codes = jnp.zeros(owner_seq.shape, jnp.int8)
        return jax.lax.scan(body_faulted, state,
                            (batches, owner_seq, keys, fault_codes),
                            unroll=unroll)

    return run


def _member_mask(mask, like):
    """(G,) bool -> broadcastable against a (G, ...) stacked leaf."""
    return mask.reshape((-1,) + (1,) * (like.ndim - 1))


def _write_bank_rows(bank, rows, owner_idx):
    """Scatter a GROUP of rows at once. `owner_idx` entries are distinct
    among valid members (the conflict-free partition guarantees it);
    padded members carry an out-of-range index and are dropped."""
    if isinstance(bank, jax.Array):    # flat (N, P) bank
        return bank.at[owner_idx].set(rows.astype(bank.dtype), mode="drop")
    return jax.tree_util.tree_map(
        lambda leaf, v: leaf.at[owner_idx].set(v.astype(leaf.dtype), mode="drop"),
        bank, rows)


def make_group_rounds(loss_fn, cfg: AsyncDPConfig,
                      scales: Optional[jax.Array] = None, mesh=None):
    """Owner-parallel multi-round driver: a dynamic-trip-count loop over
    CONFLICT-FREE round groups, vmap over the members of each group.

    Returns run(state, batches, owner_seq, keys, group_idx, group_valid,
    n_groups) -> (state, metrics) where batches/owner_seq/keys are the
    (K,)-leading inputs of `make_fused_rounds` and (group_idx,
    group_valid) are the (rows, G_max) arrays from `schedules.pack_groups`
    — group_idx[g] holds the round indices of group g, group_valid masks
    padding. The group axis may be padded with fully-invalid rows for
    jit-cache shape stability; `n_groups` (a TRACED count, so it never
    recompiles) bounds a `fori_loop`, so the padded rows NEVER execute —
    before this, every padded group still paid the full (N, P) bank
    loop-carry copy of one scan step, the single largest per-step cost at
    MLP scale. Metrics come back GROUP-MAJOR ((rows, G_max) leading, the
    never-executed padded rows zero-filled) — the session scatters them
    back to round order.

    Semantics vs the sequential scan, for groups whose owners are all
    distinct (the partition's invariant):

      * Ledger spend is EXACTLY sequential. Authorization depends only on
        the owner's prior grant count, and an owner appears at most once
        per group, so every member sees the same count it would have seen
        sequentially. Spent/refused land via a disjoint scatter.
      * Bank rows are disjoint: each granted member writes its own
        eq.(5) copy computed from the group-entry theta_L.
      * theta_L takes ONE inertia reduction per group: the mean of the
        granted members' eq.(7) targets. The mean of projected targets
        stays inside Theta (convex), and for a single granted member
        reduces to sum/1.0 — exactly that member's sequential update.
        For larger groups every member sees the group-entry theta_L
        instead of its sequential predecessor: a bounded deviation of
        the same character as the paper's own asynchrony (stale reads),
        measured in the benchmarks and tests, NOT a change to the noise
        or the privacy accounting.
    """
    compute = _round_compute(loss_fn, cfg, scales, mesh=mesh)
    n_owners = cfg.n_owners

    def vmap_rounds(theta_L, bank, tr, batch_g, owners, keys_g, slots,
                    stale_w=None):
        """vmapped round compute over the group members. `slots` is the
        per-member hot-slot vector for paged banks, None otherwise (the
        non-paged call chain is verbatim — no extra traced operand);
        `stale_w` is the per-member (G,) decay-weight vector when the
        staleness decay is armed, statically absent otherwise.
        Returns (new_L, new_i, theta_i, metrics, new_rows, rows_t)."""
        if stale_w is not None:
            # decayed-inertia variant: the same per-member calls with the
            # weight vector mapped alongside
            if tr is not None:
                if slots is None:
                    rows_t, cnts = jax.vmap(
                        lambda o: _tree_row_of(tr, o))(owners)
                    new_L, new_i, theta_i, metrics, new_rows = jax.vmap(
                        lambda b, o, k, r, c, w: compute(
                            theta_L, bank, b, o, k, tree_row=r,
                            tree_count=c, stale_w=w))(
                            batch_g, owners, keys_g, rows_t, cnts, stale_w)
                else:
                    rows_t, cnts = jax.vmap(
                        lambda o, s: _tree_row_of(tr, o, s))(owners, slots)
                    new_L, new_i, theta_i, metrics, new_rows = jax.vmap(
                        lambda b, o, k, r, c, s, w: compute(
                            theta_L, bank, b, o, k, tree_row=r,
                            tree_count=c, row_idx=s, stale_w=w))(
                            batch_g, owners, keys_g, rows_t, cnts, slots,
                            stale_w)
                return new_L, new_i, theta_i, metrics, new_rows, rows_t
            if slots is None:
                new_L, new_i, theta_i, metrics, _ = jax.vmap(
                    lambda b, o, k, w: compute(theta_L, bank, b, o, k,
                                               stale_w=w))(
                        batch_g, owners, keys_g, stale_w)
            else:
                new_L, new_i, theta_i, metrics, _ = jax.vmap(
                    lambda b, o, k, s, w: compute(theta_L, bank, b, o, k,
                                                  row_idx=s, stale_w=w))(
                        batch_g, owners, keys_g, slots, stale_w)
            return new_L, new_i, theta_i, metrics, None, None
        if tr is not None:
            # distinct owners per group (the partition's invariant), so
            # the per-member tree rows are disjoint reads AND writes
            if slots is None:
                rows_t, cnts = jax.vmap(
                    lambda o: _tree_row_of(tr, o))(owners)
                new_L, new_i, theta_i, metrics, new_rows = jax.vmap(
                    lambda b, o, k, r, c: compute(theta_L, bank, b, o, k,
                                                  tree_row=r,
                                                  tree_count=c))(
                        batch_g, owners, keys_g, rows_t, cnts)
            else:
                rows_t, cnts = jax.vmap(
                    lambda o, s: _tree_row_of(tr, o, s))(owners, slots)
                new_L, new_i, theta_i, metrics, new_rows = jax.vmap(
                    lambda b, o, k, r, c, s: compute(
                        theta_L, bank, b, o, k, tree_row=r, tree_count=c,
                        row_idx=s))(batch_g, owners, keys_g, rows_t,
                                    cnts, slots)
            return new_L, new_i, theta_i, metrics, new_rows, rows_t
        if slots is None:
            new_L, new_i, theta_i, metrics, _ = jax.vmap(
                lambda b, o, k: compute(theta_L, bank, b, o, k))(
                    batch_g, owners, keys_g)
        else:
            new_L, new_i, theta_i, metrics, _ = jax.vmap(
                lambda b, o, k, s: compute(theta_L, bank, b, o, k,
                                           row_idx=s))(
                    batch_g, owners, keys_g, slots)
        return new_L, new_i, theta_i, metrics, None, None

    def scatter_indices(bank, owners, valid, slots, hit_g):
        """(idx_w, idx_c): the row-scatter and safe-gather index vectors.

        Non-paged banks index rows by owner (pad -> the n_owners drop
        sentinel). Paged banks index by hot slot, and members that
        MISSED drop from the scatter entirely: distinct owners can clamp
        to the SAME slot on a miss, so the write-own-row-back idiom
        could collide — dropping is the same bit-exact no-op."""
        if slots is None:
            return (jnp.where(valid, owners, n_owners),
                    jnp.where(valid, owners, 0))
        resident = valid & hit_g
        return (jnp.where(resident, slots, bank.n_hot),
                jnp.where(resident, slots, 0))

    def write_rows(bank, new_i, theta_i, keys_g, grant, idx_w, idx_c,
                   slots):
        """Scatter the group's bank rows; members without `grant`
        (refused, padded, guard-rejected) write their own row back
        unchanged."""
        hot = bank.hot if slots is not None else bank
        with jax.named_scope("fed.bank_write"):
            if isinstance(hot, QuantBank):
                # error feedback under member-parallelism: members chain
                # the shared residual IN ROUND ORDER (groups are
                # consecutive runs of the schedule), exactly as the fused
                # scan would — encode every member against the carried
                # residual, advance the carry only on a grant.
                # Bit-identical to the sequential driver; a fully-refused
                # group leaves the residual untouched.
                def _ef_chain(res, inp):
                    v, k, g = inp
                    c_n, s_n, err = _encode_bank_row(hot, v + res, k,
                                                     cfg.privatizer)
                    return jnp.where(g, err, res), (c_n, s_n)

                residual, (codes_n, scales_n) = jax.lax.scan(
                    _ef_chain, hot.residual, (new_i, keys_g, grant))
                codes_w = jnp.where(_member_mask(grant, codes_n), codes_n,
                                    hot.codes[idx_c])
                scales_w = jnp.where(grant[:, None], scales_n,
                                     hot.scales[idx_c])
                new_hot = QuantBank(
                    hot.codes.at[idx_w].set(codes_w, mode="drop"),
                    hot.scales.at[idx_w].set(scales_w, mode="drop"),
                    residual, hot.codec)
            else:
                rows = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(_member_mask(grant, a), a, b),
                    new_i, theta_i)
                new_hot = _write_bank_rows(hot, rows, idx_w)
        return bank.replace(hot=new_hot) if slots is not None else new_hot

    def write_tree(tr, new_rows, rows_t, grant, idx_w, owners_w):
        """Scatter the members' noise-tree rows (refused/padded members
        scatter their own row back unchanged) and bump the leaf counters
        by the grant bits."""
        if tr is None:
            return None
        with jax.named_scope("fed.noise"):
            rows_m = jax.tree_util.tree_map(
                lambda a, b: jnp.where(_member_mask(grant, a), a, b),
                new_rows, rows_t)
            nodes = jax.tree_util.tree_map(
                lambda leaf, v: leaf.at[idx_w].set(v, mode="drop"),
                tr.nodes, rows_m)
            return tr.replace(nodes=nodes,
                              counts=tr.counts.at[owners_w].add(
                                  grant.astype(jnp.int32), mode="drop"))

    def reduce_theta_L(new_L, theta_L, grant):
        """The single inertia reduction: the mean of the granted
        members' eq.(7) targets (theta_L unchanged if none is granted)."""
        with jax.named_scope("fed.noise"):
            n_ok = jnp.sum(grant.astype(jnp.float32))
            denom = jnp.maximum(n_ok, 1.0)

            def reduce(stacked, base):
                s = jnp.sum(jnp.where(_member_mask(grant, stacked), stacked,
                                      jnp.zeros_like(stacked)),
                            axis=0) / denom
                return jnp.where(n_ok > 0, s.astype(base.dtype), base)

            return jax.tree_util.tree_map(reduce, new_L, theta_L)

    def body(state: AsyncDPState, xs):
        batch_g, owners, keys_g, valid = xs
        led = state.ledger
        tr = state.tree
        sh = _flat_shardings_for(mesh, state.theta_L, state.bank)
        theta_L, bank = state.theta_L, state.bank
        with jax.named_scope("fed.bank_read"):
            slots, hit_g = (jax.vmap(bank.lookup)(owners)      # (G,)
                            if isinstance(bank, PagedBank) else (None, None))
        with jax.named_scope("fed.ledger"):
            ok = jax.vmap(led.authorized)(owners) & valid      # (G,)
            if hit_g is not None:
                # residency folds into the grant BEFORE the ledger
                # update — a miss spends nothing and lands in `refused`
                # (see the fused driver's body)
                ok = ok & hit_g
            oki = ok.astype(jnp.int32)

        # fully-invalid groups are jit-cache shape padding only; the
        # dynamic trip count in run() means they never reach this body,
        # so every executed group has at least one valid member
        new_L, new_i, theta_i, metrics, new_rows, rows_t = vmap_rounds(
            theta_L, bank, tr, batch_g, owners, keys_g, slots)

        owners_w = jnp.where(valid, owners, n_owners)          # pad -> drop
        idx_w, idx_c = scatter_indices(bank, owners, valid, slots, hit_g)
        bank = write_rows(bank, new_i, theta_i, keys_g, ok, idx_w, idx_c,
                          slots)
        tr = write_tree(tr, new_rows, rows_t, ok, idx_w, owners_w)
        theta_L = reduce_theta_L(new_L, theta_L, ok)
        theta_L, bank, tr = _constrain_state(theta_L, bank, tr, sh)
        with jax.named_scope("fed.ledger"):
            ledger = led.replace(
                spent=led.spent.at[owners_w].add(oki, mode="drop"),
                refused=led.refused.at[owners_w].add(
                    (valid & ~ok).astype(jnp.int32), mode="drop"))
        metrics = dict(metrics)
        metrics.update(refused=~ok, owner=owners)
        return AsyncDPState(theta_L, bank, state.step + jnp.sum(oki),
                            ledger, tr, state.faults, state.stale), metrics

    def body_faulted(state: AsyncDPState, xs):
        # fault-armed group: the per-member grant algebra of the fused
        # driver's faulted body, vectorized over the group members.
        # Distinct owners per group (the partition's invariant) keep the
        # per-owner gathers (quarantine flags, checksums, windows) and
        # every scatter disjoint, and the tumbling windows key on each
        # owner's own contact count, so grouping never moves a window
        # boundary.
        batch_g, owners, keys_g, valid, fcodes_g = xs
        led = state.ledger
        fs = state.faults
        ss = state.stale
        policy = cfg.fault_policy
        spolicy = cfg.staleness
        tr = state.tree
        sh = _flat_shardings_for(mesh, state.theta_L, state.bank)
        theta_L, bank = state.theta_L, state.bank
        with jax.named_scope("fed.bank_read"):
            slots, hit_g = (jax.vmap(bank.lookup)(owners)      # (G,)
                            if isinstance(bank, PagedBank) else (None, None))
        with jax.named_scope("fed.ledger"):
            led_auth = jax.vmap(led.authorized)(owners)
            if hit_g is not None:
                # a page miss refuses like budget exhaustion (see the
                # fused driver's faulted body)
                led_auth = led_auth & hit_g
        quar = fs.quarantined[owners]
        if ss is not None:
            # each member's round position within the dispatch: groups
            # are consecutive runs of the schedule and members sit in
            # round order, so the valid-rank offset from the group-entry
            # clock IS the sequential round index (ages/`last_grant`
            # stamps match the fused scan exactly; per-owner counters
            # are group-entry reads, exact because owners are distinct
            # within a group)
            t_g = ss.clock + jnp.cumsum(valid.astype(jnp.int32)) - 1
            in_backoff = ss.cooldown[owners] > 0
            is_retry = valid & ~quar & in_backoff
            avail = ~quar & ~in_backoff
        else:
            t_g = None
            is_retry = None
            avail = ~quar
        auth = led_auth & avail & valid                        # (G,)
        is_drop = fcodes_g == _faults.DROP
        answered = auth & ~is_drop
        stale_w = None
        if ss is not None and spolicy.decay != 1.0:
            stale_w = staleness_weight(ss, owners, t_g, spolicy)
        with jax.named_scope("fed.bank_read"):
            if slots is None:
                payload_ok = jax.vmap(
                    lambda o, c: _faults.verify_row(fs.checksum, bank, o,
                                                    c))(
                    owners, fcodes_g == _faults.CORRUPT_PAYLOAD)
            else:
                payload_ok = jax.vmap(
                    lambda o, c, s: _faults.verify_row(fs.checksum, bank, o,
                                                       c, row_idx=s))(
                    owners, fcodes_g == _faults.CORRUPT_PAYLOAD, slots)

        new_L, new_i, theta_i, metrics, new_rows, rows_t = vmap_rounds(
            theta_L, bank, tr, batch_g, owners, keys_g, slots,
            stale_w=stale_w)
        with jax.named_scope("fed.noise"):
            new_i = _faults.inject_nonfinite(
                new_i, fcodes_g == _faults.NONFINITE_GRAD)
            finite = jax.vmap(_faults.finite_guard)((new_i, new_L))
        guard_ok = payload_ok & finite & (fcodes_g != _faults.STALE)
        on_time = deadline_guard(fcodes_g)
        apply = answered & guard_ok & on_time
        timed = answered & ~on_time
        guard_rej = answered & on_time & ~guard_ok

        owners_w = jnp.where(valid, owners, n_owners)          # pad -> drop
        idx_w, idx_c = scatter_indices(bank, owners, valid, slots, hit_g)
        # a NaN-poisoned member never advances the quantized bank's
        # residual carry: its `apply` is False by the finite guard
        bank = write_rows(bank, new_i, theta_i, keys_g, apply, idx_w, idx_c,
                          slots)
        tr = write_tree(tr, new_rows, rows_t, apply, idx_w, owners_w)
        theta_L = reduce_theta_L(new_L, theta_L, apply)
        theta_L, bank, tr = _constrain_state(theta_L, bank, tr, sh)
        with jax.named_scope("fed.bank_write"):
            fs = _faults.update_checksum(fs, bank, owners, apply,
                                         row_idx=slots)
        with jax.named_scope("fed.ledger"):
            upd = dict(
                spent=led.spent.at[owners_w].add(
                    answered.astype(jnp.int32), mode="drop"),
                refused=led.refused.at[owners_w].add(
                    (valid & avail & ~led_auth).astype(jnp.int32),
                    mode="drop"),
                dropped=led.dropped.at[owners_w].add(
                    (auth & is_drop).astype(jnp.int32), mode="drop"),
                faulted=led.faulted.at[owners_w].add(
                    guard_rej.astype(jnp.int32), mode="drop"),
                quarantined=led.quarantined.at[owners_w].add(
                    (valid & quar).astype(jnp.int32), mode="drop"),
                timed_out=led.timed_out.at[owners_w].add(
                    timed.astype(jnp.int32), mode="drop"))
            if ss is not None:
                upd["retried"] = led.retried.at[owners_w].add(
                    is_retry.astype(jnp.int32), mode="drop")
            ledger = led.replace(**upd)
        # see the fused body: timeouts/retries are not quarantine events
        fs = _faults.fault_tick(fs, owners, guard_rej | (auth & is_drop),
                                policy, active=valid & avail)
        metrics = dict(metrics)
        metrics.update(refused=valid & avail & ~led_auth,
                       dropped=auth & is_drop, faulted=guard_rej,
                       quarantined=valid & quar, timed_out=timed,
                       owner=owners)
        if ss is not None:
            metrics.update(retried=is_retry)
            ss = staleness_tick(ss, owners, t_g, is_retry=is_retry,
                                apply=apply, timed=timed, policy=spolicy,
                                active=valid,
                                ticks=jnp.sum(valid.astype(jnp.int32)))
        return AsyncDPState(theta_L, bank,
                            state.step + jnp.sum(apply.astype(jnp.int32)),
                            ledger, tr, fs, ss), metrics

    def run(state: AsyncDPState, batches, owner_seq, keys, group_idx,
            group_valid, n_groups=None, fault_codes=None):
        if state.ledger is None:
            raise ValueError(
                "grouped rounds need a device ledger on the state; build "
                "the state with init_state / Federation.init_state")
        _require_tree(cfg, state)
        if state.faults is None:
            if fault_codes is not None:
                raise ValueError(
                    "fault codes need a fault-armed state; build the "
                    "config with fault_policy=FaultPolicy(...)")
            b = body
            extra = ()
        else:
            _require_fault_policy(cfg, state)
            _require_staleness(cfg, state)
            if fault_codes is None:
                fault_codes = jnp.zeros(owner_seq.shape, jnp.int8)
            b = body_faulted
            extra = (fault_codes[group_idx],)
        xs = (jax.tree_util.tree_map(lambda a: a[group_idx], batches),
              owner_seq[group_idx], keys[group_idx], group_valid) + extra
        rows = group_idx.shape[0]
        if rows == 0:
            return jax.lax.scan(b, state, xs)          # empty dispatch
        if n_groups is None:
            n_groups = rows
        # dynamic trip count: the group axis is padded to a shape bucket
        # for the jit cache, but only the real groups execute — metrics
        # land in pre-allocated group-major buffers via one-row updates,
        # the padded rows stay zero (and masked-out downstream)
        m_shape = jax.eval_shape(
            lambda s, x: b(s, x)[1], state,
            jax.tree_util.tree_map(lambda a: a[0], xs))
        mets0 = jax.tree_util.tree_map(
            lambda sd: jnp.zeros((rows,) + sd.shape, sd.dtype), m_shape)

        def body_at(g, carry):
            st, mets = carry
            xg = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, g, 0,
                                                       keepdims=False), xs)
            st, m = b(st, xg)
            mets = jax.tree_util.tree_map(
                lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                    buf, v, g, 0), mets, m)
            return st, mets

        return jax.lax.fori_loop(0, n_groups, body_at, (state, mets0))

    return run


def make_sync_dp_step(loss_fn, cfg: AsyncDPConfig, lr: float,
                      scales: Optional[jax.Array] = None):
    """Synchronous DP-SGD baseline (the paper's related-work comparator,
    [12]/[14]-style): every owner contributes a privatized gradient each
    round; the learner averages them. Used by benchmarks to quantify what
    asynchrony costs/buys.

    step(params, batches, key, weights=None): `weights` (N,) rescales each
    owner's contribution — the Federation session passes 0/1 liveness there
    so budget-exhausted owners drop out of the round.

    The per-owner accumulation is a `lax.scan` over the stacked (N, B, ...)
    batches, so trace size and compile time stay O(1) in N (the unrolled
    Python loop grew both linearly — prohibitive at hundreds of owners).
    The scan body accumulates in the same owner order with the same ops as
    the old loop, so results are unchanged.
    """
    if cfg.tree_depth is not None:
        raise ValueError(
            "the synchronous baseline draws independent per-round noise; "
            "the tree mechanism (cfg.tree_depth) has no sync counterpart")
    scales = _noise_scales(cfg) if scales is None else jnp.asarray(
        scales, jnp.float32)
    n_i = jnp.asarray(cfg.owner_sizes, jnp.float32)
    n = float(cfg.n_total)

    def step(params, batches, key, weights=None):
        keys = jax.random.split(key, cfg.n_owners)
        w_all = (n_i / n if weights is None
                 else weights * n_i / n)                       # (N,)

        def body(acc, xs):
            b_i, k_i, s_i, w_i = xs
            q, _ = private_grad(loss_fn, params, b_i, k_i,
                                cfg=cfg.privatizer, noise_scale=s_i)
            return jax.tree_util.tree_map(
                lambda a, g: a + w_i * g.astype(jnp.float32), acc, q), None

        zeros = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(leaf.shape, jnp.float32), params)
        acc, _ = jax.lax.scan(body, zeros, (batches, keys, scales, w_all))
        reg = jax.tree_util.tree_map(
            lambda leaf: cfg.sigma * leaf.astype(jnp.float32), params)
        new = jax.tree_util.tree_map(
            lambda p, g, r: (p - lr * (g + r).astype(p.dtype)).astype(p.dtype),
            params, acc, reg)
        return jax.tree_util.tree_map(
            lambda leaf: jnp.clip(leaf, -cfg.theta_max, cfg.theta_max), new)

    return step
